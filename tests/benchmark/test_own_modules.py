"""A configuration brings its reference and its arithmetic as data: a tiny
Mixtral-shaped configuration is served by the program's ``mixtral`` family on
the CPU and held against a reference module and a shapes module that exist
nowhere but in the test's temporary directory.  No file of the benchmark
knows an expert layer."""

import json
import textwrap

import pytest

from benchmark import modules, run as runner

from .helpers import TINY_MIX, tiny_bench

TINY_MIXTRAL = dict(
    model_type="mixtral", vocab_size=2000, hidden_size=64, intermediate_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    num_local_experts=4, num_experts_per_tok=2, rms_norm_eps=1e-5, rope_theta=1e4,
    tie_word_embeddings=False)   # no max_position_embeddings: the served context stands in
# set from the readings ``drive`` prints: at this size (CPU, PR 29) the bf16
# program read topk_err_mean 0.0059-0.0114, logprob_err_mean 0.0044-0.0051 and
# gap_max 0.0015-0.130 over seeds 11, 12, 13, 2**31 + 5 and 987654321 (seed
# 11 reads twice the others in the first and 0.130 in the last: a token whose
# router sat at a tie took another expert in bfloat16); with the reference's
# second expert dropped 0.272-0.321, 0.216-0.246 and 0.94-2.04
LIMITS = {"gap_max": 0.4, "logprob_err_mean": 0.03, "topk_err_mean": 0.05,
          "min_checked_tokens": 100, "min_probed_tokens": 40}

MOE_REFERENCE = textwrap.dedent('''
    """A Mixtral-shaped decoder in plain jax.numpy: llama attention, and in
    place of the MLP a router (softmax over all experts, the first k
    renormalised) over E SwiGLU experts, computed densely.  Weights from the
    recipe the program states for its mixtral family served without a
    checkpoint."""
    import math

    import jax
    import jax.numpy as jnp

    HIGHEST = jax.lax.Precision.HIGHEST
    TOP_K_USED = None   # the altered copy sets 1: the second expert dropped


    def _dims(hf):
        heads = hf["num_attention_heads"]
        return dict(h=hf["hidden_size"], i=hf["intermediate_size"], l=hf["num_hidden_layers"],
                    heads=heads, kv=hf["num_key_value_heads"],
                    d=hf.get("head_dim") or hf["hidden_size"] // heads, v=hf["vocab_size"],
                    e=hf["num_local_experts"], k=hf["num_experts_per_tok"],
                    eps=hf.get("rms_norm_eps", 1e-5), theta=hf.get("rope_theta", 1e6))


    def init_weights(hf, seed):
        c = _dims(hf)
        keys = jax.random.split(jax.random.PRNGKey(seed), 12)
        h, i, l, e, qd, kvd = c["h"], c["i"], c["l"], c["e"], c["heads"] * c["d"], c["kv"] * c["d"]
        draw = lambda key, shape, fan: (jax.random.normal(key, shape, jnp.float32)
                                        / jnp.sqrt(fan)).astype(jnp.bfloat16)
        return {"embed": draw(keys[0], (c["v"], h), 1.0),
                "wq": draw(keys[1], (l, h, qd), h), "wk": draw(keys[2], (l, h, kvd), h),
                "wv": draw(keys[3], (l, h, kvd), h), "wo": draw(keys[4], (l, qd, h), qd),
                "w_router": draw(keys[5], (l, h, e), h), "w_gate": draw(keys[6], (l, e, h, i), h),
                "w_up": draw(keys[7], (l, e, h, i), h), "w_down": draw(keys[8], (l, e, i, h), i),
                "lm_head": draw(keys[9], (h, c["v"]), h)}


    def quantize(leaves, kind, hf):
        if kind != "fp8":
            raise KeyError(kind)

        def fp8(w):
            w32 = w.astype(jnp.float32)
            scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 240.0
            q = jax.lax.reduce_precision(w32 / jnp.maximum(scale, 1e-30), exponent_bits=4,
                                         mantissa_bits=3)
            return (q * scale).astype(jnp.bfloat16)

        # the embedding is only looked up, and the router decides in float32
        return {n: (w if n in ("embed", "w_router") else fp8(w)) for n, w in leaves.items()}


    def _rms(x, eps):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


    def _rope(x, cos, sin):
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


    def _mm(a, b):
        return jnp.matmul(a, b.astype(jnp.float32), precision=HIGHEST)


    def _experts(m, w, c):
        probs = jax.nn.softmax(_mm(m, w["w_router"]), axis=-1)
        top, ids = jax.lax.top_k(probs, c["k"])
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        if TOP_K_USED:
            top = top.at[:, TOP_K_USED:].set(0.0)
        share = jnp.zeros_like(probs).at[jnp.arange(m.shape[0])[:, None], ids].set(top)
        up = jnp.einsum("th,ehi->tei", m, w["w_up"].astype(jnp.float32), precision=HIGHEST)
        gate = jnp.einsum("th,ehi->tei", m, w["w_gate"].astype(jnp.float32), precision=HIGHEST)
        out = jnp.einsum("tei,eih->teh", jax.nn.silu(gate) * up,
                         w["w_down"].astype(jnp.float32), precision=HIGHEST)
        return jnp.einsum("te,teh->th", share, out, precision=HIGHEST)


    def hidden(weights, hf, ids):
        c = _dims(hf)
        ids = jnp.asarray(ids, jnp.int32)
        t, half = ids.shape[0], c["d"] // 2
        pos = jnp.arange(t)
        freqs = 1.0 / (c["theta"] ** (jnp.arange(0, half, dtype=jnp.float32) / half))
        ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        mask = pos[:, None] >= pos[None, :]
        x = weights["embed"][ids].astype(jnp.float32)
        for layer in range(c["l"]):
            w = {n: a[layer] for n, a in weights.items() if n not in ("embed", "lm_head")}
            a = _rms(x, c["eps"])
            q = _rope(_mm(a, w["wq"]).reshape(t, c["heads"], c["d"]), cos, sin)
            k = _rope(_mm(a, w["wk"]).reshape(t, c["kv"], c["d"]), cos, sin)
            v = _mm(a, w["wv"]).reshape(t, c["kv"], c["d"])
            qg = q.reshape(t, c["kv"], c["heads"] // c["kv"], c["d"])
            s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HIGHEST) / math.sqrt(c["d"])
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST).reshape(t, -1)
            x = x + _mm(o, w["wo"])
            x = x + _experts(_rms(x, c["eps"]), w, c)
        return x


    def logits(weights, hf, x):
        return _mm(_rms(x, _dims(hf)["eps"]), weights["lm_head"])
''')

MOE_SHAPES = textwrap.dedent('''
    """The arithmetic of a Mixtral-shaped configuration: every expert is held,
    a token multiplies against k of them."""
    BF16 = 2


    def _attn(hf):
        h, heads = hf["hidden_size"], hf["num_attention_heads"]
        d = hf.get("head_dim") or h // heads
        return 2 * h * heads * d + 2 * h * hf["num_key_value_heads"] * d, d


    def _expert(hf):
        return 3 * hf["hidden_size"] * hf["intermediate_size"]


    def total_params(hf):
        h, e = hf["hidden_size"], hf["num_local_experts"]
        layer = _attn(hf)[0] + h * e + e * _expert(hf) + 2 * h
        return hf["num_hidden_layers"] * layer + 2 * hf["vocab_size"] * h + h


    def matmul_params(hf):
        h = hf["hidden_size"]
        layer = _attn(hf)[0] + h * hf["num_local_experts"] + hf["num_experts_per_tok"] * _expert(hf)
        return hf["num_hidden_layers"] * layer + hf["vocab_size"] * h


    def weight_bytes(hf):
        """A step of many lanes streams every expert once."""
        return BF16 * (total_params(hf) - hf["vocab_size"] * hf["hidden_size"])


    def kv_bytes_per_token(hf):
        return 2 * hf["num_hidden_layers"] * hf["num_key_value_heads"] * _attn(hf)[1] * BF16


    def cache_bytes(hf, serving):
        args = serving["args"]
        return int(args[args.index("--num-blocks") + 1]) * 16 * kv_bytes_per_token(hf)


    def flops_per_token(hf):
        return 2 * matmul_params(hf)
''')


def drive(tmp_path, seed, reference=MOE_REFERENCE):
    bench = tiny_bench(tmp_path, TINY_MIXTRAL, LIMITS, name="moe",
                       own_modules={"reference": reference, "shapes": MOE_SHAPES})
    mix = dict(TINY_MIX, output_tokens={"dist": "lognormal", "median": 40, "sigma": 0.3,
                                        "min": 24, "max": 64}, check_requests=6)
    (tmp_path / "traffic" / "tinychat.json").write_text(json.dumps(mix))
    dump = tmp_path / "dump.json"
    argv = ["--workload", "moe.tinychat", "--seed", str(seed), "--seconds", "3", "--trace", "0",
            "--dump", str(dump)]
    rc, result = runner.run(runner.parse(argv), require_platform=None, bench_path=bench,
                            bench_dir=tmp_path, env_overlay={"JAX_PLATFORMS": "cpu"})
    check = json.loads(dump.read_text())["check"]
    print(f"seed {seed}: gap_max {check['gap_max']:.4f} logprob_err_mean "
          f"{check['logprob_err_mean']:.5f} topk_err_mean {check['topk_err_mean']:.5f}")
    return rc, result, check


@pytest.mark.parametrize("seed", [2**31 + 5])
def test_a_mixtral_shaped_configuration_is_served_and_held_to_modules_of_its_own(tmp_path, seed):
    rc, result, check = drive(tmp_path, seed)
    assert rc == 0 and result["failed"] == 0 and result["correct"] is True
    # the run reached its end: the cell's next run is a warm one
    assert all(m.exists() for m in runner.warm_markers(runner.cache_dir(), "moe.tinychat"))
    assert check["tokens"] >= LIMITS["min_checked_tokens"]
    assert check["probed_tokens"] >= LIMITS["min_probed_tokens"]
    assert check["topk_err_mean"] < LIMITS["topk_err_mean"] / 2
    # the served config.json is the published keys; the tokenizer's length
    # is the served context where the configuration publishes none
    served = runner.WORK / "moe.tinychat" / "model"
    assert set(json.loads((served / "config.json").read_text())) == set(TINY_MIXTRAL)
    assert json.loads((served / "tokenizer_config.json").read_text())["model_max_length"] == 256
    # nothing of this lives under benchmark/
    loaded = runner.load_cell(json.loads((tmp_path / "BENCHMARK.json").read_text()),
                              "moe.tinychat", tmp_path)
    assert loaded["reference"].parent == tmp_path / "reference"
    assert not (runner.BENCH_DIR / "reference" / loaded["reference"].name).exists()
    assert not (runner.BENCH_DIR / f"{loaded['config']['shapes']}.py").exists()


def test_an_altered_expert_layer_in_that_reference_is_not_correct(tmp_path):
    rc, result, check = drive(tmp_path, 11, MOE_REFERENCE.replace("TOP_K_USED = None", "TOP_K_USED = 1"))
    assert rc == 0 and result["failed"] == 0
    assert result["correct"] is False
    assert check["topk_err_mean"] > 2 * LIMITS["topk_err_mean"]


def test_the_shares_read_the_configuration_own_arithmetic(tmp_path):
    bench_path = tiny_bench(tmp_path, TINY_MIXTRAL, LIMITS, name="moe",
                            own_modules={"reference": MOE_REFERENCE, "shapes": MOE_SHAPES})
    bench = json.loads(bench_path.read_text())
    loaded = runner.load_cell(bench, "moe.tinychat", tmp_path)
    hf, own = runner.hf_config(loaded["config"]), loaded["shapes"]
    llama = modules.load(runner.BENCH_DIR / "shapes.py")
    # four experts held, two of them multiplied against
    assert own.total_params(hf) > llama.total_params(hf) + 2 * 3 * 2 * 64 * 96
    assert own.flops_per_token(hf) == 2 * (2 * (2 * 64 * 64 + 2 * 64 * 32 + 64 * 4 + 2 * 3 * 64 * 96)
                                          + 2000 * 64)
    assert own.cache_bytes(hf, loaded["config"]["serving"]) == 128 * 16 * 2 * 2 * 2 * 16 * 2
    ctx = {"records": [], "seconds": 4.0, "hf": hf, "shapes": own, "e2e": {"tok_per_s": 1000.0},
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "stats0": {"stats": {}}, "stats1": {"stats": {}}}
    got = runner.read_per_layer(bench, "moe.tinychat", ctx, tmp_path)
    assert got["model_flops_share.long"]["value"] == pytest.approx(
        100.0 * own.flops_per_token(hf) * 1000.0 / 1e12)
    # without a shapes module there is nothing to read, and nothing is read
    assert "model_flops_share.long" not in runner.read_per_layer(
        bench, "moe.tinychat", dict(ctx, shapes=None), tmp_path)
