"""One general traffic generator, driven by a mix's data file.

``schedule(mix, rate, seconds)`` fixes WHEN requests are due and HOW LONG
their prompts and answers are, from the mix's own ``schedule_seed``: every
``--seed`` offers the same arrivals and sizes in the same order, so two seeds
ask the same work of the server.  ``fill(...)`` then draws what the seed owns,
the token ids of every prompt and shared prefix.  (The engine's time does not
depend on which ids it is given; the order of sizes does move a tail, which
is why it is not the seed's to change.  PERF.md section 2 says more.)

The vocabulary is the harness's own word-level one (token i is the word
``t<i>``), so a prompt of n words is exactly n tokens.  Ids below
``RESERVED`` belong to the chat template.
"""

from __future__ import annotations

import math

import numpy as np

RESERVED = 8  # t0..t5 are the template's markers, t6 is the unknown word


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from ``spec``: a clipped log-normal, or a constant."""
    if spec["dist"] == "constant":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def schedule(mix: dict, rate: float, seconds: float) -> list[dict]:
    """Due instants (seconds from the window's start; the lead-in is
    negative), prompt and output lengths and the shared prefix of each
    request.  A pure function of the mix, the rate and the window."""
    # one stream per quantity, so the sizes do not depend on the rate
    streams = [np.random.default_rng([int(mix["schedule_seed"]), k]) for k in range(5)]
    lead = float(mix.get("lead_in_s", 0.0))
    horizon = lead + seconds
    n_max = int(rate * horizon * 2 + 64)
    arrivals = mix["arrivals"]
    if arrivals["process"] == "poisson":
        gaps = streams[0].exponential(1.0 / rate, n_max)
    elif arrivals["process"] == "uniform":
        gaps = np.full(n_max, 1.0 / rate)
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    due = np.cumsum(gaps)
    # a mix offered above the knee may open on a backlog: its first requests
    # are due one after another from the lead-in's first instant (spaced so
    # that they queue in this order), and the arrivals start after the last
    # of them.  The queue then
    # never runs empty, so which request the server takes next is decided by
    # the queue's order and not by which of two instants came first.
    backlog = mix.get("backlog") or {}
    n_back = int(backlog.get("requests", 0))
    if n_back:
        step = float(backlog.get("spacing_s", 0.1))
        due = np.concatenate([np.arange(n_back) * step, n_back * step + due])[:n_max]
    prompts = _lengths(streams[1], mix["prompt_tokens"], n_max)
    outputs = _lengths(streams[2], mix["output_tokens"], n_max)
    shared = mix.get("shared_prefix")
    prefix = np.full(n_max, -1, np.int64)
    if shared:
        ranks = np.arange(1, shared["count"] + 1, dtype=np.float64)
        weights = ranks ** -float(shared.get("zipf_s", 1.0))
        picks = streams[3].choice(shared["count"], n_max, p=weights / weights.sum())
        prefix = np.where(streams[4].random(n_max) < shared["share"], picks, -1)
    # the arrivals next after the window closes are the probes: the same
    # kind of request, asked to return its first k tokens' log-probabilities
    # (which moves its lane to the synchronous path, so none is sent inside)
    n_window = int(np.count_nonzero(due < horizon))
    probes = mix.get("probes") or {}
    n_all = n_window + int(probes.get("requests", 0))
    return [
        {"index": int(i), "due": float(due[i] - lead), "prompt_len": int(prompts[i]),
         "output_len": int(outputs[i]), "prefix": int(prefix[i]),
         "probe": int(probes.get("top_logprobs", 20)) if i >= n_window else 0}
        for i in range(n_all)
    ]


def fill(plan: list[dict], mix: dict, vocab_size: int, seed: int) -> dict:
    """Token ids for the plan, from ``seed``: ``{"prefixes": [[ids]...],
    "prompts": {index: [ids]}}``."""
    rng = np.random.default_rng(int(seed))
    shared = mix.get("shared_prefix")
    prefixes = []
    if shared:
        prefixes = [
            rng.integers(RESERVED, vocab_size, shared["tokens"]).tolist()
            for _ in range(shared["count"])
        ]
    prompts = {
        r["index"]: rng.integers(RESERVED, vocab_size, r["prompt_len"]).tolist()
        for r in plan
    }
    return {"prefixes": prefixes, "prompts": prompts}


def words(ids) -> str:
    return " ".join(f"t{i}" for i in ids)


def ids_of(text: str) -> list[int]:
    """The ids a returned text names (every word is ``t<i>``)."""
    return [int(w[1:]) for w in text.split()]


# The chat template the harness writes beside the model, and the same thing
# as ids, for the reference.  t0 opens, t2/t3/t4 mark system/user/assistant,
# t5 closes a turn.  None of them is a "special" token: the detokenizer
# prints every id it is given, so a served text names every served token.
CHAT_TEMPLATE = (
    "{{ 't0' }}{% for message in messages %}"
    "{% if message.role == 'system' %}{{ ' t2 ' + message.content + ' t5' }}"
    "{% elif message.role == 'user' %}{{ ' t3 ' + message.content + ' t5' }}"
    "{% elif message.role == 'assistant' %}{{ ' t4 ' + message.content + ' t5' }}"
    "{% endif %}{% endfor %}{% if add_generation_prompt %}{{ ' t4' }}{% endif %}"
)


def messages(request: dict, filled: dict) -> list[dict]:
    out = []
    if request["prefix"] >= 0:
        out.append({"role": "system", "content": words(filled["prefixes"][request["prefix"]])})
    out.append({"role": "user", "content": words(filled["prompts"][request["index"]])})
    return out


def templated_ids(request: dict, filled: dict) -> list[int]:
    """What the preprocessor must hand the engine for this request."""
    ids = [0]
    if request["prefix"] >= 0:
        ids += [2, *filled["prefixes"][request["prefix"]], 5]
    ids += [3, *filled["prompts"][request["index"]], 5, 4]
    return ids
