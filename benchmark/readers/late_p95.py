"""How late the generator ran: 95th percentile of sent - due, in ms."""

from benchmark import arith


def read(ctx):
    late = [(r["sent"] - r["due"]) * 1e3 for r in ctx["records"]
            if r["sent"] is not None and 0.0 <= r["due"] < ctx["seconds"]]
    return arith.percentile(late, 95) if late else None
