"""The comparison that decides ``correct``, run as a child once the server
has gone (a chip belongs to one process).

    python benchmark/reference/check.py <job.json> <out.json>

The job holds the configuration's ``config.json`` keys, the file of the
reference module the configuration names (``benchmark/README.md`` has the
interface; nothing here knows a reference, a leaf or a way of generating by
name), the weights' seed and a sample of finished requests: the ids the
engine must have been handed and the ids it served.  For each, ONE reference
trunk over prompt + served tokens; its served rows then go through the head
``PAD`` at a time, however many there are, and give, at every served
position, how far the served token's logit lies below the reference's best,
in units of that row's logit spread (the two configurations' logits differ
fifty-fold in scale).  WHICH row decided served token ``a`` is row
``n - 1 + a`` of that trunk (the row before it: next-token generation)
unless the module offers ``decided_by``: then the module hands over the
deciding rows itself, teacher-forced on the path the server reported
(``served_passes``: for each served token, the pass over its block that
fixed it).
``control`` (``fp8``) also puts the reference at that precision in the
program's place and reads the same numbers of it.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

PAD = 256    # sequences are padded to a multiple of this; rows go through the head this many at a time
TOP_K = 20


def _row_stats(logits, served, top_tokens):
    """Per served row: the gap of the served token under the reference's
    best (in units of the row's logit spread), the reference's log-probability
    of it, the spread, and the reference's logits at the server's first k."""
    import jax
    import jax.numpy as jnp

    sigma = jnp.std(logits, axis=-1)
    at = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    lsm = at - jax.nn.logsumexp(logits, axis=-1)
    return {"gap": (jnp.max(logits, axis=-1) - at) / sigma, "logprob": lsm, "sigma": sigma,
            "first": jnp.argmax(logits, axis=-1),
            "at_top": jnp.take_along_axis(logits, top_tokens, axis=-1)}


def _control_stats(logits, low):
    """The control in the program's place: the token IT ranks first, its
    log-probability of it, and its first k logits, each beside the
    reference's reading of the same tokens."""
    import jax
    import jax.numpy as jnp

    vals, toks = jax.lax.top_k(low, TOP_K)
    first = toks[:, 0]
    ref_first = jnp.take_along_axis(logits, first[:, None], axis=-1)[:, 0]
    sigma = jnp.std(logits, axis=-1)
    return {"gap": (jnp.max(logits, axis=-1) - ref_first) / sigma,
            "logprob_err": jnp.abs((vals[:, 0] - jax.nn.logsumexp(low, axis=-1))
                                   - (ref_first - jax.nn.logsumexp(logits, axis=-1))),
            "top_values": vals, "at_top": jnp.take_along_axis(logits, toks, axis=-1)}


def _topk_err(values, at_top, sigma):
    """Differences among the first k values are differences of logits: held
    against the reference's differences over the same tokens."""
    import numpy as np

    got = values - values[:, :1]
    want = at_top - at_top[:, :1]
    return np.abs(got - want)[:, 1:] / sigma[:, None]


def _slices(s: dict, decided: bool = False):
    """A sample's served rows, ``PAD`` at a time: the rows of the trunk's
    output (of the module's own deciding rows where it handed those over:
    one a served token, in the answer's order), the served ids and the
    probe's first k, each padded to ``PAD``, and how many of them are real."""
    import numpy as np

    n, m = len(s["prompt_ids"]), len(s["served_ids"])
    top = s.get("top")
    k = min((len(r) for r in top), default=0) if top else 0
    for a in range(0, m, PAD):
        real = min(PAD, m - a)
        rows = np.zeros(PAD, np.int32)
        rows[:real] = np.arange(n - 1 + a, n - 1 + a + real)
        if decided:
            rows[:real] = np.arange(a, a + real)
        served = np.zeros(PAD, np.int32)
        served[:real] = s["served_ids"][a:a + real]
        top_tokens = np.zeros((PAD, max(k, 1)), np.int32)
        top_values = np.zeros((PAD, max(k, 1)), np.float32)
        if k:
            top_tokens[:real] = [[t for t, _ in r[:k]] for r in top[a:a + real]]
            top_values[:real] = [[v for _, v in r[:k]] for r in top[a:a + real]]
        yield real, rows, served, top_tokens, top_values


def _path(s: dict):
    """What a module with ``decided_by`` is handed of a sample.  A server
    that did not say how it decoded cannot be ``correct``: the child fails."""
    passes = s.get("served_passes")
    if passes is None or len(passes) != len(s["served_ids"]):
        raise SystemExit(f"sample {s['index']}: the reference decides a served token by the pass that "
                         f"fixed it, and the server reported no decided_at for every token it served")
    return s["prompt_ids"], s["served_ids"], passes


def run(job: dict) -> dict:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import jax.numpy as jnp
    import numpy as np

    from benchmark import modules

    ref = modules.load(job["reference"])
    device = jax.devices()[0]
    if job.get("require_platform") and device.platform != job["require_platform"]:
        raise SystemExit(f"reference needs {job['require_platform']}, found {device.platform}")
    hf = job["hf"]
    t0 = time.monotonic()
    weights = ref.init_weights(hf, job["weights_seed"])
    jax.block_until_ready(weights)
    print(f"weights after {time.monotonic() - t0:.1f} s", flush=True)
    row_stats, control_stats = jax.jit(_row_stats), jax.jit(_control_stats)
    cat = lambda parts: {a: np.concatenate([p[a] for p in parts]) for a in parts[0]}  # noqa: E731
    # one padded length for every sample and PAD rows for every slice: one
    # set of programs, whatever the seed drew and however long an answer is
    longest = max(len(s["prompt_ids"]) + len(s["served_ids"]) for s in job["samples"])
    length = longest + (-longest % PAD)
    # a module that says itself which row decided a served token (a token
    # fixed by a pass over its block): asked in place of the row before it
    decided_by = getattr(ref, "decided_by", None)
    out, kept = [], []
    for s in job["samples"]:
        ids = s["prompt_ids"] + s["served_ids"]
        n, m = len(s["prompt_ids"]), len(s["served_ids"])
        padded = ids + [0] * (length - len(ids))
        if decided_by:
            x = decided_by(weights, hf, *_path(s), length)
        else:
            x = ref.hidden(weights, hf, padded)
        parts, top_values, held = [], [], []
        for real, rows, served, top_tokens, values in _slices(s, bool(decided_by)):
            logits = ref.logits(weights, hf, x[jnp.asarray(rows)])
            parts.append({a: np.asarray(b)[:real] for a, b in
                          row_stats(logits, jnp.asarray(served), jnp.asarray(top_tokens)).items()})
            top_values.append(values[:real])
            if job.get("control"):
                # on the host: the chip holds the weights, and a long
                # answer's logits are gigabytes
                held.append((real, rows, np.asarray(logits)))
        st, top_values = cat(parts), np.concatenate(top_values)
        rec = {"index": s["index"], "tokens": m, "gap_max": float(st["gap"].max()),
               "gap_mean": float(st["gap"].mean()),
               "mismatch": int((st["first"] != np.asarray(s["served_ids"])).sum())}
        if s.get("served_logprobs"):
            err = np.abs(np.asarray(s["served_logprobs"], np.float32) - st["logprob"])
            rec["logprob_err_max"], rec["logprob_err_mean"] = float(err.max()), float(err.mean())
        if top_values.shape[1] > 1:
            e = _topk_err(top_values, st["at_top"], st["sigma"])
            rec["topk_err_max"], rec["topk_err_mean"] = float(e.max()), float(e.mean())
        if job.get("control"):
            kept.append((rec, s, padded, held, st["sigma"]))
        out.append(rec)
        print(f"sample {s['index']} ({n}+{m} tokens) after {time.monotonic() - t0:.1f} s", flush=True)
    if job.get("control"):
        # the control's weights take the place of the reference's, leaf by
        # leaf (the chip does not hold both); the module says what a leaf is
        control = {}
        for name in list(weights):
            control.update(ref.quantize({name: weights.pop(name)}, job["control"], hf))
        for rec, s, padded, held, sigma in kept:
            if decided_by:
                x = decided_by(control, hf, *_path(s), length)
            else:
                x = ref.hidden(control, hf, padded)
            cs = cat([{a: np.asarray(b)[:real] for a, b in control_stats(
                jnp.asarray(logits), ref.logits(control, hf, x[jnp.asarray(rows)])).items()}
                for real, rows, logits in held])
            ce = _topk_err(cs["top_values"], cs["at_top"], sigma)
            rec.update(control_gap_max=float(cs["gap"].max()), control_gap_mean=float(cs["gap"].mean()),
                       control_logprob_err_max=float(cs["logprob_err"].max()),
                       control_logprob_err_mean=float(cs["logprob_err"].mean()),
                       control_topk_err_max=float(ce.max()), control_topk_err_mean=float(ce.mean()))
        print(f"control after {time.monotonic() - t0:.1f} s", flush=True)
    total = sum(r["tokens"] for r in out) or 1
    summary = {
        "samples": out, "tokens": total,
        "gap_max": max((r["gap_max"] for r in out), default=0.0),
        "gap_mean": sum(r["gap_mean"] * r["tokens"] for r in out) / total,
        "mismatch": sum(r["mismatch"] for r in out),
        "device": {"platform": device.platform, "kind": device.device_kind},
    }
    for key in ("logprob_err", "topk_err", "control_gap", "control_logprob_err", "control_topk_err"):
        have = [r for r in out if key + "_max" in r]
        if have:
            summary[key + "_max"] = max(r[key + "_max"] for r in have)
            summary[key + "_mean"] = (sum(r[key + "_mean"] * r["tokens"] for r in have)
                                      / sum(r["tokens"] for r in have))
    summary["probed_tokens"] = sum(r["tokens"] for r in out if "topk_err_max" in r)
    return summary


def main(argv) -> int:
    job = json.loads(Path(argv[0]).read_text())
    Path(argv[1]).write_text(json.dumps(run(job)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
