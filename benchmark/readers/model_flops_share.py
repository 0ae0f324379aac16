"""An end-to-end utilization, named as such: 2 x the parameters a token
multiplies against x tokens a second finished in the window, over the chip's
published bf16 peak (%).  The count is the configuration's own
(``ctx["shapes"]``, the module its file names)."""


def read(ctx):
    if not ctx.get("peaks") or "tok_per_s" not in ctx["e2e"] or ctx.get("shapes") is None:
        return None
    rate = ctx["shapes"].flops_per_token(ctx["hf"]) * ctx["e2e"]["tok_per_s"]
    return 100.0 * rate / ctx["peaks"]["bf16_flops_per_s"]
