"""Prompt tokens served from the prefix cache, as a share of the prompt
tokens sent in the window (%)."""

from benchmark.readers.counter_delta import delta


def read(ctx):
    hit = delta(ctx, "prefix_cached_tokens_total")
    sent = sum(r["prompt_tokens"] for r in ctx["records"]
               if r["sent"] is not None and 0.0 <= r["sent"] <= ctx["seconds"])
    if hit is None or not sent:
        return None
    return 100.0 * hit / sent
