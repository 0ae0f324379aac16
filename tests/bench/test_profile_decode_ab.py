"""scripts/profile_decode.py --ab under tier-1: the decode-pipeline A/B
gate runs in-process on the tiny model (same pattern as
tests/robustness/test_chaos_smoke.py), so every CI run proves the
sync-vs-overlap harness still measures both modes and that the overlapped
pipeline actually dispatches feedback windows.

Throughput on a shared CI box is noisy, so the smoke passes a zero
speedup floor — regression gating is for the real profiling harness
(``--ab`` with the default ``--ab-min-speedup 1.0``)."""

import asyncio
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).parent.parent.parent / "scripts"))


def ab_args(**overrides) -> SimpleNamespace:
    defaults = dict(
        model="tiny", quant="none", kv_dtype="bf16", isl=32, osl=12,
        batch=4, decode_steps=2, overlap=None, ab=True,
        ab_min_speedup=0.0, out=None,
    )
    defaults.update(overrides)
    return SimpleNamespace(**defaults)


async def test_profile_decode_ab_smoke():
    from profile_decode import amain

    rc, result = await amain(ab_args())
    assert rc == 0
    assert result["ab"] is True
    # both modes ran the same workload and the report carries the shares
    # the acceptance gate reads
    assert result["sync"]["overlap"] is False
    assert result["overlap"]["overlap"] is True
    assert result["sync"]["windows_overlapped"] == 0
    assert result["overlap"]["windows_overlapped"] > 0
    # `readback` is the host blocked on the device in both modes (under
    # overlap the wait is for the previous window); always accounted, and
    # every report carries the same six phases
    assert result["readback_share_overlap"] > 0.0
    assert result["readback_share_sync"] > 0.0
    for mode in ("sync", "overlap"):
        assert set(result[mode]["phase_ms"]) == {
            "schedule", "pack", "upload", "dispatch", "readback", "post"}
    assert result["overlap_speedup_tok_s"] > 0.0


async def test_profile_decode_single_mode():
    """--overlap 0 forces the synchronous path in a plain (non-A/B) run."""
    from profile_decode import amain

    rc, result = await amain(ab_args(ab=False, overlap=0, osl=8))
    assert rc == 0
    assert result["overlap"] is False
    assert result["windows_overlapped"] == 0
