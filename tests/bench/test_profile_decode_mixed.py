"""scripts/profile_decode.py --mixed under tier-1: the continuous-arrival
mixed prefill+decode A/B (split step vs ragged unified-batch step) runs
in-process on the tiny model, proving the harness measures both modes, that
the unified engine actually serves ragged windows, and that admission never
drains the unified pipeline.

Throughput on a shared CI box is noisy, so the smoke passes a zero speedup
floor — regression gating is for the real profiling harness (``--mixed``
with the default ``--mixed-min-speedup 1.0``), whose refreshed result lives
in PROFILE_DECODE.json."""

import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).parent.parent.parent / "scripts"))


def mixed_args(**overrides) -> SimpleNamespace:
    defaults = dict(
        model="tiny", quant="none", kv_dtype="bf16", isl=32, osl=10,
        batch=4, decode_steps=1, overlap=None, ab=False,
        ab_min_speedup=0.0, mixed=True, mixed_min_speedup=0.0,
        requests=6, arrival_ms=30, chunk=16, out=None,
        family="llama", decode_heavy=False,
    )
    defaults.update(overrides)
    return SimpleNamespace(**defaults)


def _assert_mixed_ok(rc, result):
    assert rc == 0
    assert result["mixed"] is True
    # both modes ran the arrival stream and the report carries the numbers
    # the acceptance gate reads
    assert result["split"]["mode"] == "split"
    assert result["unified"]["mode"] == "unified"
    assert result["split"]["steps_s"] > 0
    assert result["unified"]["steps_s"] > 0
    # the unified engine really served mixed windows through one dispatch...
    assert result["windows_unified"] > 0
    assert result["split"]["windows_unified"] == 0
    # ...and new-sequence admission never drained its pipeline
    assert result["admission_drains_unified"] == 0
    assert result["unified_speedup_steps_s"] > 0.0


async def test_profile_decode_mixed_smoke():
    from profile_decode import amain

    rc, result = await amain(mixed_args())
    _assert_mixed_ok(rc, result)
    assert result["family"] == "llama"


async def test_profile_decode_mixed_moe_family():
    """--family moe: the Mixtral routed-expert unified forward serves the
    same continuous-arrival A/B end to end."""
    from profile_decode import amain

    rc, result = await amain(
        mixed_args(family="moe", isl=16, osl=6, requests=4, batch=4)
    )
    _assert_mixed_ok(rc, result)
    assert result["family"] == "moe"
    assert result["model"] == "tiny_moe"


async def test_profile_decode_mixed_mla_family():
    """--family mla: the DeepSeek latent-KV unified forward serves the
    same continuous-arrival A/B end to end."""
    from profile_decode import amain

    rc, result = await amain(
        mixed_args(family="mla", isl=16, osl=6, requests=4, batch=4)
    )
    _assert_mixed_ok(rc, result)
    assert result["family"] == "mla"
    assert result["model"] == "tiny_mla"


async def test_profile_decode_mixed_decode_heavy():
    """--decode-heavy: burst admission packs the window with decode lanes;
    the unified engine still serves ragged windows and never drains."""
    from profile_decode import amain

    rc, result = await amain(
        mixed_args(decode_heavy=True, osl=16, requests=4, batch=4)
    )
    _assert_mixed_ok(rc, result)
    assert result["decode_heavy"] is True
