#!/usr/bin/env python3
"""Does the main path run on the chip?  The quickest proof, kept with the repo.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # one four-chip host: the tensor-parallel path

One chip: starts the server the way the README does —
``python -m dynamo_tpu.cli.run run in=http out=jax --model-path <dir> --warmup``
— as a CHILD process on Llama-3.2-3B (published config, full depth, bf16,
weights random from the engine's seed), sends it chat completions over real
HTTP (unary, streamed, one prompt of more than 2,000 tokens admitted while
short requests decode), stops it, and starts it a second time with the same
command to serve the same traffic from the now-warm compile cache.
Passes only if the child served from a TPU with the Pallas kernels, every
request returned tokens, mixed unified windows were dispatched with no
fallback reason, the weights sat on the chip, the second start compiled
nothing, and greedy tokens agree with the same model served on the CPU
backend (same seed, XLA attention) on a small input.

Four chips (``--chips 4``) runs only the sharded path and what it is compared
with: Llama-3-8B bf16 (16 GB of weights — more than one chip holds) at tp=4
through the same entry point, then an 8-layer cut of it served at tp=4 and on
one chip from the same seed, whose greedy tokens must agree.

This process never imports JAX: a chip belongs to one process, and that is the
server.  The device and the engine's counters are read from what the server
logs (``jax devices: …`` at start, ``engine stopped: …`` with stats() at stop).
The last line of stdout is one JSON object: {"ok": …, "device": {…}}.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
DEADLINE_S = 1150  # the driver allows 1200
FOUR_CHIP_DEADLINE_S = 3300  # run by the builder: three server starts

# Published configs (config.json of meta-llama/Llama-3.2-3B and
# meta-llama/Meta-Llama-3-8B); the same widths as LlamaConfig.llama32_3b() /
# llama3_8b().  bos/eos follow the synthetic tokenizer written beside them.
_COMMON = {
    "model_type": "llama", "vocab_size": 128256, "head_dim": 128,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-5, "rope_theta": 500000.0,
    "bos_token_id": 0, "eos_token_id": 1, "torch_dtype": "bfloat16",
}
LLAMA32_3B = {
    **_COMMON, "hidden_size": 3072, "intermediate_size": 8192,
    "num_hidden_layers": 28, "num_attention_heads": 24,
    "max_position_embeddings": 131072, "tie_word_embeddings": True,
    "rope_scaling": {
        "factor": 32.0, "high_freq_factor": 4.0, "low_freq_factor": 1.0,
        "original_max_position_embeddings": 8192, "rope_type": "llama3",
    },
}
LLAMA3_8B = {
    **_COMMON, "hidden_size": 4096, "intermediate_size": 14336,
    "num_hidden_layers": 32, "num_attention_heads": 32,
    "max_position_embeddings": 8192, "tie_word_embeddings": False,
}

SPECIALS = ["<|bos|>", "<|eos|>", "<|sys|>", "<|user|>", "<|asst|>", "<|end|>"]
CHAT_TEMPLATE = (
    "{{ '<|bos|>' }}{% for message in messages %}"
    "{% if message.role == 'system' %}{{ '<|sys|> ' + message.content + ' <|end|>' }}"
    "{% elif message.role == 'user' %}{{ '<|user|> ' + message.content + ' <|end|>' }}"
    "{% elif message.role == 'assistant' %}{{ '<|asst|> ' + message.content + ' <|end|>' }}"
    "{% endif %}{% endfor %}{% if add_generation_prompt %}{{ '<|asst|>' }}{% endif %}"
)


def say(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


# -- model directory ---------------------------------------------------------

def write_model_dir(path: Path, config: dict) -> None:
    """config.json + a word-level tokenizer over the model's whole vocab
    (token i is the word ``t<i>``), so prompts have exact token counts and
    the returned text names the sampled ids.  No safetensors: the server
    random-initialises from the engine seed."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import WhitespaceSplit

    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps(config, indent=1))
    vocab = {tok: i for i, tok in enumerate(SPECIALS)}
    for i in range(len(SPECIALS), config["vocab_size"]):
        vocab[f"t{i}"] = i
    tk = Tokenizer(WordLevel(vocab, unk_token="t6"))
    tk.pre_tokenizer = WhitespaceSplit()
    tk.add_special_tokens(SPECIALS)
    tk.save(str(path / "tokenizer.json"))
    (path / "tokenizer_config.json").write_text(json.dumps({
        "model_type": "llama", "bos_token": "<|bos|>", "eos_token": "<|eos|>",
        "chat_template": CHAT_TEMPLATE,
        "model_max_length": config["max_position_embeddings"],
    }))


def words(n: int, salt: int) -> str:
    """n distinct-ish vocabulary words, deterministic in (n, salt)."""
    return " ".join(f"t{1000 + (salt * 7919 + i * 104729) % 120000}" for i in range(n))


# -- the server child --------------------------------------------------------

class Server:
    def __init__(self, tag: str, model_dir: Path, extra: list[str], deadline: float,
                 env_overlay: dict | None = None):
        self.tag = tag
        self.deadline = deadline
        self.log_path = OUT / f"server-{tag}.log"
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        self.port = s.getsockname()[1]
        s.close()
        env = dict(os.environ)
        env["DYN_LOG"] = "info"
        env["PYTHONUNBUFFERED"] = "1"
        env.update(env_overlay or {})
        # the engine builds params on the host CPU backend, so that backend
        # has to exist beside whatever platform the environment names; order
        # (and therefore the default backend) is left as it is
        plats = env.get("JAX_PLATFORMS", "")
        if plats and "cpu" not in plats.split(","):
            env["JAX_PLATFORMS"] = plats + ",cpu"
        self.cmd = [
            sys.executable, "-m", "dynamo_tpu.cli.run", "run", "in=http",
            "out=jax", "--model-path", str(model_dir), "--model-name", "smoke",
            "--host", "127.0.0.1", "--port", str(self.port), *extra,
        ]
        say(f"[{tag}] starting: {' '.join(self.cmd[1:])}")
        self.t0 = time.monotonic()
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            self.cmd, cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self.marks: dict[str, float] = {}

    def _text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def wait_for(self, needle: str, what: str) -> str:
        """Block until a log line contains ``needle``; returns that line."""
        while True:
            for line in self._text().splitlines():
                if needle in line:
                    self.marks[what] = time.monotonic() - self.t0
                    return line
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"[{self.tag}] server died (rc={self.proc.returncode}) "
                    f"before {what}; tail of {self.log_path.name}:\n{self.tail()}"
                )
            if time.monotonic() > self.deadline:
                raise SmokeFailure(f"[{self.tag}] out of time waiting for {what}")
            time.sleep(0.5)

    def json_after(self, needle: str, what: str) -> dict:
        line = self.wait_for(needle, what)
        return json.loads(line[line.index(needle) + len(needle):])

    def tail(self, n: int = 25) -> str:
        return "\n".join(self._text().splitlines()[-n:])

    def stop(self) -> dict | None:
        """SIGINT → the CLI shuts the worker down → the engine logs its
        final stats.  Returns them (None if the server never got that far)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                say(f"[{self.tag}] server ignored SIGINT; killing")
        self.kill()
        needle = "engine stopped: "
        for line in self._text().splitlines():
            if needle in line:
                return json.loads(line[line.index(needle) + len(needle):])
        return None

    def kill(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self.log.close()


# -- requests ----------------------------------------------------------------

def chat(port: int, content: str, max_tokens: int, *, stream: bool = False,
         top_logprobs: int = 0) -> dict:
    """One /v1/chat/completions call.  Returns prompt/completion token
    counts, the text, and (when asked) per-token logprob entries."""
    body = {
        "model": "smoke", "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0.0, "stream": stream,
        "ext": {"ignore_eos": True},
    }
    if stream:
        body["stream_options"] = {"include_usage": True}
    if top_logprobs:
        body["logprobs"] = True
        body["top_logprobs"] = top_logprobs
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(body).encode(), headers={"Content-Type": "application/json"},
    )
    t0 = time.monotonic()
    out = {"text": "", "prompt_tokens": 0, "completion_tokens": 0,
           "chunks": 0, "logprobs": [], "stream": stream}
    with urllib.request.urlopen(req, timeout=900) as resp:
        if not stream:
            data = json.loads(resp.read())
            choice = data["choices"][0]
            out["text"] = choice["message"].get("content") or ""
            out["logprobs"] = (choice.get("logprobs") or {}).get("content") or []
            usage = data.get("usage") or {}
        else:
            usage = {}
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data:") or line == "data: [DONE]":
                    continue
                data = json.loads(line[5:])
                usage = data.get("usage") or usage
                for choice in data.get("choices") or []:
                    out["chunks"] += 1
                    out["text"] += (choice.get("delta") or {}).get("content") or ""
    out["prompt_tokens"] = int(usage.get("prompt_tokens", 0))
    out["completion_tokens"] = int(usage.get("completion_tokens", 0))
    out["seconds"] = round(time.monotonic() - t0, 2)
    return out


def traffic(port: int) -> list[dict]:
    """Three short requests start decoding, the long prompt is admitted
    while they run (a mixed prefill+decode window), two more short ones (one
    streamed) follow while the long one decodes, and a last unary one runs
    alone.  Seven requests."""
    plan = [  # (delay s, name, prompt words, max_tokens, stream)
        (0.0, "short-a", 24, 96, False),
        (0.0, "short-b", 40, 96, True),
        (0.0, "short-c", 56, 96, False),
        (1.0, "long-2000", 2000, 48, False),
        (2.5, "short-d", 30, 32, True),
        (2.5, "short-e", 70, 32, False),
        (0.0, "alone", 12, 16, False),
    ]
    results: list[dict] = [{} for _ in plan]

    def run(i: int) -> None:
        delay, name, n, max_tokens, stream = plan[i]
        time.sleep(delay)
        try:
            results[i] = {"name": name, **chat(port, words(n, i), max_tokens, stream=stream)}
        except Exception as exc:  # noqa: BLE001 — reported and failed by check_results
            results[i] = {"name": name, "error": f"{type(exc).__name__}: {exc}"}

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(plan) - 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    run(len(plan) - 1)
    return results


def check_results(tag: str, results: list[dict]) -> list[str]:
    problems = []
    for r in results:
        if "error" in r:
            problems.append(f"[{tag}] request {r['name']} failed: {r['error']}")
            continue
        say(f"[{tag}] {r['name']}: prompt_tokens={r['prompt_tokens']} "
            f"completion_tokens={r['completion_tokens']} stream={r['stream']} "
            f"chunks={r['chunks']} {r['seconds']}s text={r['text'][:40]!r}")
        if r["completion_tokens"] <= 0:
            problems.append(f"[{tag}] request {r['name']} returned no tokens")
        if r["stream"] and r["chunks"] < 2:
            problems.append(f"[{tag}] streamed request {r['name']} came in {r['chunks']} chunk(s)")
    long_ones = [r for r in results if r.get("prompt_tokens", 0) >= 2000]
    if not long_ones:
        problems.append(f"[{tag}] no prompt of >= 2000 tokens was served")
    return problems


# -- one server life: start, serve, stop, judge ------------------------------

def serve_once(tag: str, model_dir: Path, extra: list[str], deadline: float, *,
               require_platform: str | None = "tpu", driver=traffic,
               env_overlay: dict | None = None):
    """Returns (device, stats, results, marks).  Raises SmokeFailure when the
    server is not on ``require_platform``, dies, or runs out of time."""
    server = Server(tag, model_dir, extra, deadline, env_overlay)
    try:
        device = server.json_after("jax devices: ", "jax_up")
        say(f"[{tag}] device: {json.dumps(device)}")
        if require_platform and device["platform"] != require_platform:
            raise SmokeFailure(
                f"[{tag}] server runs on {device['platform']!r}, not "
                f"{require_platform!r}", device,
            )
        server.wait_for("engine loop started", "engine_init")
        server.wait_for("listening on http://", "ready")
        t = time.monotonic()
        results = driver(server.port)
        server.marks["serving"] = time.monotonic() - t
        if server.proc.poll() is not None:
            raise SmokeFailure(f"[{tag}] server died while serving:\n{server.tail()}")
        stats = server.stop()
        if stats is None:
            raise SmokeFailure(f"[{tag}] server logged no final stats:\n{server.tail()}")
        m = server.marks
        say(f"[{tag}] seconds: process+jax {m['jax_up']:.1f} | engine init "
            f"(host params, upload) {m['engine_init'] - m['jax_up']:.1f} | "
            f"warmup+compile {m['ready'] - m['engine_init']:.1f} | "
            f"serving {m['serving']:.1f}")
        return device, stats, results, m
    finally:
        server.kill()


def param_bytes(c: dict) -> int:
    """bf16 bytes of the llama param tree for a config.json."""
    h, i, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    per_layer = 2 * h * qd + 2 * h * kvd + 3 * h * i + 2 * h
    embeds = v * h * (1 if c["tie_word_embeddings"] else 2)
    return 2 * (c["num_hidden_layers"] * per_layer + embeds + h)


def report_stats(tag: str, stats: dict) -> None:
    keys = (
        "attention_impl", "kernel_config", "decode_windows_unified_total",
        "unified_fallbacks", "admission_drains_total",
        "decode_windows_overlapped_total", "decode_windows_sync_total",
        "compile_requests_total", "compile_cache_hits_total", "compiles_total",
        "tokens_emitted_total", "num_preemptions_total", "device_bytes_in_use",
    )
    say(f"[{tag}] engine: " + json.dumps({k: stats.get(k) for k in keys}))


def one_chip(config: dict = LLAMA32_3B, *, name: str = "llama-3.2-3b",
             require_platform: str | None = "tpu",
             extra: tuple[str, ...] = ("--num-blocks", "1024", "--max-batch-size", "8",
                                       "--context-length", "4096")) -> tuple[bool, dict]:
    deadline = time.monotonic() + DEADLINE_S
    model_dir = OUT / name
    write_model_dir(model_dir, config)
    say(f"model: {name} layers={config['num_hidden_layers']} hidden={config['hidden_size']} "
        f"heads={config['num_attention_heads']}/{config['num_key_value_heads']} bf16 "
        f"({param_bytes(config) / 2**30:.2f} GiB), weights random from the engine seed")
    from dynamo_tpu.native import native_status

    say(f"csrc natives: {json.dumps(native_status())}")
    problems: list[str] = []
    device: dict = {}
    on_chip: list[dict] = []
    # cold: --warmup compiles and runs every serving program (all unified
    # buckets, decode, prefill).  warm: the same command again must find
    # every one of them in the persistent cache.  (Same command on purpose:
    # a Pallas program's cache key depends on the order in which the process
    # first traced its kernels — see PERF.md — and --warmup fixes that order.)
    args = [*extra, "--warmup"]
    for tag in ("cold", "warm"):
        device, stats, results, _ = serve_once(
            tag, model_dir, args, deadline, require_platform=require_platform,
            driver=lambda port: traffic(port) + reference_traffic(port))
        report_stats(tag, stats)
        on_chip = [r for r in results if r["name"].startswith("parity-")]
        problems += check_results(tag, [r for r in results if r not in on_chip])
        if stats.get("device") != device:
            problems.append(f"[{tag}] engine device {stats.get('device')} != {device}")
        if require_platform == "tpu" and stats.get("attention_impl") != "pallas":
            problems.append(f"[{tag}] attention_impl={stats.get('attention_impl')!r}, not 'pallas'")
        used = (stats.get("device_bytes_in_use") or [None])[0]
        if require_platform == "tpu" and (used or 0) < param_bytes(config):
            problems.append(f"[{tag}] {used} bytes in use on the chip: the "
                            f"{param_bytes(config)} bytes of weights are not resident")
        if not stats.get("decode_windows_unified_total"):
            problems.append(f"[{tag}] unified_windows == 0")
        if stats.get("unified_fallbacks"):
            problems.append(f"[{tag}] unified_fallbacks fired: {stats['unified_fallbacks']}")
        if tag == "warm" and stats.get("compiles_total") != 0:
            problems.append(
                f"[warm] second start compiled {stats.get('compiles_total')} programs "
                "(want 0: every one should come from the persistent cache)")

    # is what came out right?  The same server on the CPU backend — same
    # seed, so the same weights; XLA attention instead of the Pallas kernels
    # — answers the reference requests; greedy tokens must agree and the
    # chip's logprobs must be finite.
    say("reference: the same model served on the CPU backend (XLA attention)")
    _, _, on_cpu, _ = serve_once(
        "cpu-ref", model_dir, list(extra), deadline, require_platform="cpu",
        driver=reference_traffic, env_overlay={"JAX_PLATFORMS": "cpu"})
    for r in on_chip:
        lps = [e["logprob"] for e in r["logprobs"]]
        if not lps or any(lp != lp or lp > 1e-3 or lp < -1e4 for lp in lps):
            problems.append(f"{r['name']}: logprobs from the chip are not finite: {lps}")
    cmp_problems, same, flips = compare_greedy(on_chip, on_cpu)
    say(f"chip vs CPU reference: {same}/{len(on_chip)} requests token-exact, "
        f"{flips} parted at a near-tie")
    problems += [f"chip vs CPU reference: {p}" for p in cmp_problems]
    for p in problems:
        say("FAIL " + p)
    return not problems, device


# -- four chips --------------------------------------------------------------

def parity_traffic(port: int, plan=((12, 12), (200, 12), (40, 12))) -> list[dict]:
    """Greedy requests whose tokens are compared across two servers."""
    return [
        {"name": f"parity-{i}", **chat(port, words(n, 50 + i), max_tokens, top_logprobs=5)}
        for i, (n, max_tokens) in enumerate(plan)
    ]


def reference_traffic(port: int) -> list[dict]:
    """The small input of the reference comparison: two short greedy
    requests with logprobs (three tokens each: a 3B decode step takes ~10 s
    on the reference's CPU backend)."""
    return parity_traffic(port, plan=((10, 3), (48, 3)))


def compare_greedy(a: list[dict], b: list[dict]) -> tuple[list[str], int, int]:
    """Token-exact agreement, request by request.  Where two servers part
    ways, it must be at a near-tie: the token the other one chose is within
    0.1 nats of this one's choice in this one's own top-5 (bf16 partial sums
    reduce in a different order across four chips)."""
    problems, same, flips = [], 0, 0
    for ra, rb in zip(a, b):
        ta = [e["token"] for e in ra["logprobs"]]
        tb = [e["token"] for e in rb["logprobs"]]
        if not ta or len(ta) != len(tb):
            problems.append(f"{ra['name']}: {len(ta)} vs {len(tb)} tokens returned")
            continue
        if ta == tb:
            same += 1
            continue
        i = next(k for k in range(len(ta)) if ta[k] != tb[k])
        top = {t["token"]: t["logprob"] for t in ra["logprobs"][i].get("top_logprobs") or []}
        gap = ra["logprobs"][i]["logprob"] - top[tb[i]] if tb[i] in top else None
        if gap is not None and gap < 0.1:
            flips += 1
            say(f"{ra['name']}: agree for {i} tokens, then a near-tie ({gap:.4f} nats)")
        else:
            problems.append(f"{ra['name']}: diverge at token {i} ({ta[i]} vs {tb[i]}, gap {gap})")
    return problems, same, flips


def four_chips(full: dict = LLAMA3_8B, *, cut_layers: int = 8,
               require_platform: str | None = "tpu") -> tuple[bool, dict]:
    deadline = time.monotonic() + FOUR_CHIP_DEADLINE_S
    on_tpu = require_platform == "tpu"
    problems: list[str] = []
    full_dir, cut_dir = OUT / "full", OUT / f"cut-{cut_layers}-layers"
    write_model_dir(full_dir, full)
    write_model_dir(cut_dir, {**full, "num_hidden_layers": cut_layers})
    common = ["--num-blocks", "512", "--max-batch-size", "8", "--context-length", "4096"]
    tp4 = [*common, "--tensor-parallel-size", "4"]
    def serve(tag, model_dir, args, **kw):
        return serve_once(tag, model_dir, args, deadline,
                          require_platform=require_platform, **kw)

    say(f"(a) {full['num_hidden_layers']} layers, hidden {full['hidden_size']}, bf16, "
        "tp=4 through the same entry point")
    device, stats, results, _ = serve("tp4-full", full_dir, tp4)
    report_stats("tp4-full", stats)
    problems += check_results("tp4-full", results)
    if device.get("count") != 4:
        problems.append(f"need 4 devices, server saw {device.get('count')}")
    used = stats.get("device_bytes_in_use") or []
    say(f"[tp4-full] bytes_in_use per device: {used}")
    if on_tpu and (len(used) != 4 or min(used) <= 0 or max(used) > 2 * min(used)):
        problems.append(f"params/KV not spread over 4 devices: bytes_in_use={used}")

    say(f"(b) what it is compared with: {cut_layers}-layer cut, tp=4 vs one chip, "
        "same seed, greedy")
    _, stats4, res4, _ = serve("tp4-cut", cut_dir, tp4, driver=parity_traffic)
    _, stats1, res1, _ = serve("tp1-cut", cut_dir, common, driver=parity_traffic)
    for tag, st in (("tp4-full", stats), ("tp4-cut", stats4), ("tp1-cut", stats1)):
        if tag != "tp4-full":
            report_stats(tag, st)
        if on_tpu and st.get("attention_impl") != "pallas":
            problems.append(f"[{tag}] attention_impl={st.get('attention_impl')!r}, not 'pallas'")
    cmp_problems, same, flips = compare_greedy(res4, res1)
    say(f"greedy parity tp=4 vs tp=1: {same}/{len(res4)} requests token-exact, "
        f"{flips} parted at a near-tie")
    problems += cmp_problems
    for p in problems:
        say("FAIL " + p)
    return not problems, device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    if not (ROOT / "dynamo_tpu" / "cli" / "run.py").exists():
        print("chip_smoke.py must sit at the root of the repository", file=sys.stderr)
        return 2
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    t0 = time.monotonic()
    device: dict = {}
    try:
        ok, device = four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as exc:
        say(f"FAIL {exc.args[0]}")
        ok = False
        if len(exc.args) > 1:
            device = exc.args[1]
    say(f"total {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
