"""The model directory and the server child (after ``chip_smoke.py``'s
``write_model_dir`` and ``Server``, copied so that the smoke may change)."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

from benchmark import traffic

ROOT = Path(__file__).resolve().parents[1]
# keys of a configuration's file that are the harness's, not config.json's
NOT_HF = ("source", "reduced", "assumed", "deployment", "serving", "published", "notes",
          "limits", "reference", "shapes")


class BenchFailure(Exception):
    pass


def hf_config(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in NOT_HF}


def serving_context(config: dict) -> int:
    args = config["serving"]["args"]
    return int(args[args.index("--context-length") + 1])


def write_model_dir(path: Path, config: dict) -> None:
    """config.json + a word-level tokenizer over the whole vocabulary (token
    i is the word ``t<i>``; no special tokens, so a text names every id) +
    the chat template.  No safetensors: the server draws its weights from
    the engine's seed.  ``model_max_length`` is the published
    ``max_position_embeddings`` where the configuration has one, and the
    context it is served at otherwise."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import WhitespaceSplit

    path.mkdir(parents=True, exist_ok=True)
    hf = hf_config(config)
    (path / "config.json").write_text(json.dumps(hf, indent=1))
    vocab = {f"t{i}": i for i in range(hf["vocab_size"])}
    tk = Tokenizer(WordLevel(vocab, unk_token="t6"))
    tk.pre_tokenizer = WhitespaceSplit()
    tk.save(str(path / "tokenizer.json"))
    (path / "tokenizer_config.json").write_text(json.dumps({
        "model_type": hf.get("model_type", "llama"), "bos_token": "t0", "eos_token": "t1",
        "chat_template": traffic.CHAT_TEMPLATE,
        "model_max_length": hf.get("max_position_embeddings") or serving_context(config),
    }))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Server:
    """``launcher.py <control> <seed> run in=http out=jax --model-path <dir>
    --warmup …`` as a child in its own session."""

    def __init__(self, model_dir: Path, serving_args: list[str], seed: int,
                 log_path: Path, deadline: float, launcher: list[str] | None = None,
                 env_overlay: dict | None = None):
        self.deadline = deadline
        self.log_path = log_path
        self.port, self.control = free_port(), free_port()
        env = dict(os.environ)
        env["DYN_LOG"] = "info"
        env["PYTHONUNBUFFERED"] = "1"
        env.update(env_overlay or {})
        # the engine builds its parameters on the host CPU backend, so that
        # backend has to exist beside whatever platform the environment names
        plats = env.get("JAX_PLATFORMS", "")
        if plats and "cpu" not in plats.split(","):
            env["JAX_PLATFORMS"] = plats + ",cpu"
        launcher = launcher or [sys.executable, str(ROOT / "benchmark" / "launcher.py")]
        self.cmd = [
            *launcher, str(self.control), str(seed), "run", "in=http", "out=jax",
            "--model-path", str(model_dir), "--model-name", "bench",
            "--host", "127.0.0.1", "--port", str(self.port), *serving_args, "--warmup",
        ]
        self.t0 = time.monotonic()
        self.log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            self.cmd, cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def _text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def tail(self, n: int = 30) -> str:
        return "\n".join(self._text().splitlines()[-n:])

    def wait_for(self, needle: str, what: str) -> str:
        while True:
            for line in self._text().splitlines():
                if needle in line:
                    return line
            if self.proc.poll() is not None:
                raise BenchFailure(f"server died (rc={self.proc.returncode}) before {what}; "
                                   f"tail of its log:\n{self.tail()}")
            if time.monotonic() > self.deadline:
                raise BenchFailure(f"out of time waiting for {what}; tail:\n{self.tail()}")
            time.sleep(0.25)

    def json_after(self, needle: str, what: str) -> dict:
        line = self.wait_for(needle, what)
        return json.loads(line[line.index(needle) + len(needle):])

    def ask(self, path: str, *, post: bool = False, timeout: float = 120.0) -> dict:
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.control}{path}", method="POST" if post else "GET",
            data=b"" if post else None)
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            out = json.loads(resp.read())
        if "error" in out:
            raise BenchFailure(f"launcher {path}: {out['error']}")
        return out

    def stop(self) -> None:
        """SIGINT, then wait; the process group is killed whatever happens,
        so nothing of the server outlives this call."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self.log.close()
