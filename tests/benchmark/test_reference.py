"""The plain reference against ``models/llama.py`` at a tiny size on the CPU,
for both configurations' switches, and the comparison built on it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import check, llama_like as ref

from .helpers import TINY_MISTRAL, TINY_QWEN


def program_logits(hf, seed, ids):
    from dynamo_tpu.models import llama

    cfg = llama.LlamaConfig.from_hf_config(hf)
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    # the served weights are the bfloat16 roundings of that draw
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    cos, sin = llama.make_rope_tables(cfg)
    with jax.default_matmul_precision("highest"):
        x = llama.llama_forward_trunk(params, cfg, jnp.asarray(ids, jnp.int32),
                                      jnp.asarray(len(ids)), cos, sin)
        return llama._logits(params, cfg, x), params, cfg


@pytest.mark.parametrize("hf", [TINY_QWEN, TINY_MISTRAL], ids=["qk_norm-tied", "window-untied"])
def test_reference_agrees_with_the_program(hf):
    ids = np.random.default_rng(0).integers(8, hf["vocab_size"], 40).tolist()
    theirs, params, cfg = program_logits(hf, 3, ids)
    assert cfg.qk_norm == (hf["model_type"] == "qwen3")
    assert cfg.sliding_window == hf.get("sliding_window")
    weights = ref.init_weights(hf, 3)
    assert bool(jnp.all(params["layers"]["wq"] == weights["wq"].astype(jnp.float32)))
    mine = ref.forward(weights, hf, ids)
    assert float(jnp.max(jnp.abs(mine - theirs))) < 1e-3 * float(jnp.std(mine))
    rows = ref.forward(weights, hf, ids + [0] * 24, rows=[5, 39])
    assert float(jnp.max(jnp.abs(rows - mine[jnp.asarray([5, 39])]))) < 1e-4


def test_the_window_bites_and_the_q_k_norm_matters():
    ids = np.random.default_rng(1).integers(8, 300, 40).tolist()
    w = ref.init_weights(TINY_MISTRAL, 3)
    full = ref.forward(w, {**TINY_MISTRAL, "sliding_window": None}, ids)
    cut = ref.forward(w, TINY_MISTRAL, ids)
    assert float(jnp.max(jnp.abs(full[:8] - cut[:8]))) < 1e-5   # inside the window: same
    assert float(jnp.max(jnp.abs(full[20:] - cut[20:]))) > 1e-3
    wq = ref.init_weights(TINY_QWEN, 3)
    plain = ref.forward(wq, {**TINY_QWEN, "model_type": "qwen2"}, ids)
    assert float(jnp.max(jnp.abs(plain - ref.forward(wq, TINY_QWEN, ids)))) > 1e-3


def job_for(hf, served_shift=0, control=None):
    rng = np.random.default_rng(5)
    weights = ref.init_weights(hf, 11)
    samples = []
    for i, n in enumerate((20, 33)):
        prompt = rng.integers(8, hf["vocab_size"], n).tolist()
        served = []
        for _ in range(6):  # greedy by the reference itself
            logits = ref.forward(weights, hf, prompt + served, rows=[len(prompt) + len(served) - 1])
            served.append(int(jnp.argmax(logits[0])))
        served = [(t + served_shift) % hf["vocab_size"] for t in served]
        samples.append({"index": i, "prompt_ids": prompt, "served_ids": served})
    return {"hf": hf, "reference": ref.__file__, "weights_seed": 11, "samples": samples,
            "control": control}


@pytest.mark.parametrize("hf", [TINY_QWEN, TINY_MISTRAL], ids=["qwen", "mistral"])
def test_check_reads_no_gap_for_the_reference_own_tokens_and_a_wide_one_for_altered(hf):
    sound = check.run(job_for(hf, control="fp8"))
    assert sound["tokens"] == 12 and sound["mismatch"] == 0
    assert sound["gap_max"] == 0.0 and sound["gap_mean"] == 0.0
    assert sound["control_gap_max"] >= 0.0
    broken = check.run(job_for(hf, served_shift=1))
    assert broken["mismatch"] > 0 and broken["gap_max"] > 1.0 and broken["gap_mean"] > 0.5


def test_fp8_rounding_moves_the_weights_by_a_few_hundredths():
    w = ref.init_weights(TINY_MISTRAL, 2)
    q = ref.quantize(w, "fp8", TINY_MISTRAL)
    assert bool(jnp.all(q["embed"] == w["embed"]))  # untied: only a lookup
    tied = ref.quantize(ref.init_weights(TINY_QWEN, 2), "fp8", TINY_QWEN)
    assert not bool(jnp.all(tied["embed"] == ref.init_weights(TINY_QWEN, 2)["embed"]))

    err = jnp.abs(q["wq"].astype(jnp.float32) - w["wq"].astype(jnp.float32))
    assert 2e-2 < float(jnp.max(err) / jnp.max(jnp.abs(w["wq"].astype(jnp.float32)))) < 0.1
    with pytest.raises(KeyError):
        ref.quantize(w, "int8", TINY_MISTRAL)


def test_every_row_of_an_answer_longer_than_a_slice_is_compared():
    """3 x PAD + 1 served tokens behind a 20-token prompt: one trunk, four
    slices through the head, and the numbers are those of all 769 rows (the
    last slice holds one)."""
    hf = TINY_QWEN
    rng = np.random.default_rng(7)
    prompt = rng.integers(8, hf["vocab_size"], 20).tolist()
    served = rng.integers(8, hf["vocab_size"], 3 * check.PAD + 1).tolist()
    top = [[[int(t), float(v)] for t, v in zip(rng.integers(8, hf["vocab_size"], 4),
                                              -np.sort(rng.random(4)))] for _ in served]
    sample = {"index": 0, "prompt_ids": prompt, "served_ids": served, "top": top,
              "served_logprobs": [-1.0] * len(served)}
    job = {"hf": hf, "reference": ref.__file__, "weights_seed": 11, "samples": [sample]}
    got = check.run(job)
    assert got["tokens"] == got["probed_tokens"] == 3 * check.PAD + 1

    weights = ref.init_weights(hf, 11)
    rows = list(range(len(prompt) - 1, len(prompt) - 1 + len(served)))
    logits = np.asarray(ref.forward(weights, hf, prompt + served, rows=rows))
    at = logits[np.arange(len(served)), served]
    gap = (logits.max(axis=-1) - at) / logits.std(axis=-1)
    assert got["gap_max"] == pytest.approx(float(gap.max()), rel=1e-4)
    assert got["gap_mean"] == pytest.approx(float(gap.mean()), rel=1e-4)
    assert got["mismatch"] == int((logits.argmax(axis=-1) != np.asarray(served)).sum())
    lsm = at - np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) - logits.max(-1)
    assert got["logprob_err_mean"] == pytest.approx(float(np.abs(-1.0 - lsm).mean()), rel=1e-4)

    # the one row of the last slice counts: served there the reference's own
    # first choice, the sum of the gaps falls by exactly that row's
    again = dict(sample, served_ids=served[:-1] + [int(logits[-1].argmax())])
    fewer = check.run(dict(job, samples=[again]))
    assert fewer["mismatch"] == got["mismatch"] - 1
    assert (got["gap_mean"] - fewer["gap_mean"]) * len(served) == pytest.approx(float(gap[-1]), rel=1e-3)

    # and so does it in the control's reading, slice by slice
    low = check.run(dict(job, control="fp8"))
    assert low["gap_max"] == got["gap_max"] and low["control_topk_err_mean"] > 0.0
