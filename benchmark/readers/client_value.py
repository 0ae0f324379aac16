"""A number the client's own arithmetic has already taken (``arith.end_to_end``),
shown per layer under another name: one too unsteady to hold a bound, or the
plainer form of one that does."""


def read(ctx, key):
    return ctx["e2e"].get(key)
