"""1 - union of device-operation intervals over the traced span (%)."""


def idle(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s") or not tr.get("busy_s"):
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]


def read(ctx):
    share = idle(ctx)
    return None if share is None else 100.0 * share
