"""The server child: the program's own entry point, in a process the harness
can ask two things of.

``python benchmark/launcher.py <control-port> <engine-seed> run in=http out=jax …``
calls ``dynamo_tpu.cli.run.main`` with the arguments after the seed, exactly
what ``python -m dynamo_tpu.cli.run`` would run.  Beside it, a thread answers
on ``127.0.0.1:<control-port>``:

- ``GET /stats``       the engine's ``stats()`` and the device's memory
- ``POST /trace/start`` / ``POST /trace/stop``   ``jax.profiler`` around a
  few seconds of the window (only the process that holds the chip can trace)
- ``GET /trace/reduce?keep=<JSON list of patterns>``   the trace just taken,
  reduced (benchmark/trace.py), no operation a pattern matches dropped

The thread does nothing unless asked.  The engine's seed is handed to
``build_jax_engine`` as the ``seed`` override it already accepts
(``cli/run.py`` has no flag for it yet), so the weights follow ``--seed``.
Both kinds of run (``--trace 0`` and ``1``) start this same command, so a
cell has one compile cache.
"""

from __future__ import annotations

import json
import sys
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, HTTPServer
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

_state: dict = {"engine": None, "trace_dir": None}


def _capture_engine(seed: int) -> None:
    import dynamo_tpu.serve as serve

    build = serve.build_jax_engine

    def build_and_keep(model_dir, mdc, **overrides):
        overrides.setdefault("seed", seed)
        engine = build(model_dir, mdc, **overrides)
        _state["engine"] = engine
        return engine

    serve.build_jax_engine = build_and_keep


def _memory() -> list[dict]:
    import jax

    out = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        out.append({"peak_bytes_in_use": s.get("peak_bytes_in_use"),
                    "bytes_in_use": s.get("bytes_in_use"),
                    "bytes_limit": s.get("bytes_limit")})
    return out


class _Control(BaseHTTPRequestHandler):
    def log_message(self, *args):  # quiet
        pass

    def _reply(self, obj, code: int = 200) -> None:
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        try:
            if self.path == "/stats":
                engine = _state["engine"]
                stats = engine.stats() if engine is not None else None
                self._reply({"stats": stats, "memory": _memory()})
            elif self.path.partition("?")[0] == "/trace/reduce":
                from benchmark import trace

                query = urllib.parse.parse_qs(self.path.partition("?")[2])
                keep = json.loads(query["keep"][0]) if "keep" in query else ()
                self._reply(trace.reduce_dir(_state["trace_dir"], keep))
            else:
                self._reply({"error": "unknown path"}, 404)
        except Exception as exc:  # noqa: BLE001 - reported to the harness
            self._reply({"error": f"{type(exc).__name__}: {exc}"}, 500)

    def do_POST(self):
        import jax

        try:
            if self.path.startswith("/trace/start"):
                _state["trace_dir"] = self.path.partition("?dir=")[2]
                from benchmark import trace

                jax.profiler.start_trace(_state["trace_dir"],
                                         profiler_options=trace.start_options())
                self._reply({"ok": True})
            elif self.path == "/trace/stop":
                jax.profiler.stop_trace()
                self._reply({"ok": True})
            else:
                self._reply({"error": "unknown path"}, 404)
        except Exception as exc:  # noqa: BLE001 - reported to the harness
            self._reply({"error": f"{type(exc).__name__}: {exc}"}, 500)


def main(argv: list[str]) -> int:
    control_port, seed, rest = int(argv[0]), int(argv[1]), argv[2:]
    _capture_engine(seed)
    server = HTTPServer(("127.0.0.1", control_port), _Control)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    from dynamo_tpu.cli.run import main as run_main

    return run_main(rest)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
