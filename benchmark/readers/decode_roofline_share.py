"""The least time HBM needs for one step's bytes (the weights once + the live
KV once, from the configuration's shapes) over the device-busy time of a mean
step (%).  Some steps of a window carry a prompt and are compute-bound, so
this reads a little low; it cannot read high while a step streams the
weights at least once."""

from benchmark.readers import device_idle_share, step_ms


def read(ctx):
    step, idle = step_ms.read(ctx), device_idle_share.idle(ctx)
    live = [s["step_kv_active_blocks"] for s in ctx.get("samples") or []
            if s and s.get("step_kv_active_blocks") is not None]
    shapes = ctx.get("shapes")   # the configuration's own arithmetic
    if step is None or idle is None or not live or not ctx.get("peaks") or shapes is None:
        return None
    block = 16
    kv = sum(live) / len(live) * block * shapes.kv_bytes_per_token(ctx["hf"])
    least_s = (shapes.weight_bytes(ctx["hf"]) + kv) / ctx["peaks"]["hbm_bytes_per_s"]
    busy_s = step / 1e3 * (1.0 - idle)
    return 100.0 * least_s / busy_s if busy_s > 0 else None
