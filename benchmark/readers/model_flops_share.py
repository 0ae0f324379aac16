"""An end-to-end utilization, named as such: 2 x the parameters a token
multiplies against x tokens a second finished in the window, over the chip's
published bf16 peak (%)."""

from benchmark import shapes


def read(ctx):
    if not ctx.get("peaks") or "tok_per_s" not in ctx["e2e"]:
        return None
    rate = shapes.flops_per_token(ctx["hf"]) * ctx["e2e"]["tok_per_s"]
    return 100.0 * rate / ctx["peaks"]["bf16_flops_per_s"]
