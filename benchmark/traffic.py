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

A mix with a ``sessions`` key offers conversations: a request is then one
turn of a session and re-sends every earlier turn, with an answer the
generator drew itself (never the served one), so that the schedule and every
prompt are fixed before the run and the loop stays open.
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


def _converse(spec: dict, seed: int, due: np.ndarray, prefix: np.ndarray) -> list[dict]:
    """Which session each arrival is a turn of.  ``count`` sessions are live
    at a time; an arrival is the next turn of one of those whose previous
    turn was due at least ``think_s`` ago (drawn among them), or opens a new
    session where fewer than ``count`` are live or none has rested that long;
    a session that has had its ``turns`` leaves.  So the arrival process and
    the rate stay the mix's and the cell's, the working set is ``count``
    histories, and no turn is due sooner than ``think_s`` after the one
    before it.  A turn carries ``first_prompt_tokens`` (a session's first)
    or ``turn_tokens`` new tokens, and is followed in every later turn's
    history by ``answer_tokens_in_history`` tokens of a drawn answer."""
    n = len(due)
    rngs = [np.random.default_rng([seed, k]) for k in range(5, 10)]
    turns = rngs[0].integers(spec["turns"]["min"], spec["turns"]["max"] + 1, n)
    first = _lengths(rngs[1], spec["first_prompt_tokens"], n)
    later = _lengths(rngs[2], spec["turn_tokens"], n)
    answers = _lengths(rngs[3], spec["answer_tokens_in_history"], n)
    pick = rngs[4].random(n)
    count, think = int(spec["count"]), float(spec["think_s"])
    live: list[dict] = []
    opened, rows = 0, []
    for i in range(n):
        rested = [s for s in live if due[i] - s["last"] >= think]
        if len(live) < count or not rested:
            session = {"id": opened, "left": int(turns[i]), "history": [], "prefix": int(prefix[i])}
            opened += 1
            live.append(session)
        else:
            session = rested[int(pick[i] * len(rested))]
        rows.append({"session": session["id"], "turn": len(session["history"]),
                     "history": list(session["history"]), "prefix": session["prefix"],
                     "prompt_len": int(later[i] if session["history"] else first[i]),
                     "answer_len": int(answers[i])})
        session["history"].append(i)
        session["last"] = due[i]
        session["left"] -= 1
        if not session["left"]:
            live.remove(session)
    return rows


def schedule(mix: dict, rate: float, seconds: float) -> list[dict]:
    """Due instants (seconds from the window's start; the lead-in is
    negative), prompt and output lengths and the shared prefix of each
    request; in a mix with ``sessions`` also its session, its turn, the
    requests whose turns it re-sends (``history``: indices into this plan)
    and the length of the drawn answer that follows it there, and
    ``prompt_len`` counts the turn's new tokens.  A pure function of the
    mix, the rate and the window."""
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
    sessions = mix.get("sessions")   # its turns bring their own lengths
    prompts = np.zeros(n_max, np.int64) if sessions else _lengths(streams[1], mix["prompt_tokens"], n_max)
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
    plan = [
        {"index": int(i), "due": float(due[i] - lead), "prompt_len": int(prompts[i]),
         "output_len": int(outputs[i]), "prefix": int(prefix[i]),
         "probe": int(probes.get("top_logprobs", 20)) if i >= n_window else 0}
        for i in range(n_all)
    ]
    if sessions:
        for row, turn in zip(plan, _converse(sessions, int(mix["schedule_seed"]), due, prefix)):
            row.update(turn)
    return plan


def fill(plan: list[dict], mix: dict, vocab_size: int, seed: int) -> dict:
    """Token ids for the plan, from ``seed``: ``{"prefixes": [[ids]...],
    "prompts": {index: [ids]}}`` and, in a mix with sessions, ``"answers":
    {index: [ids]}``, the answer later turns quote after that request's."""
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
    out = {"prefixes": prefixes, "prompts": prompts}
    if mix.get("sessions"):
        out["answers"] = {
            r["index"]: rng.integers(RESERVED, vocab_size, r["answer_len"]).tolist()
            for r in plan
        }
    return out


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


MARKS = {"system": 2, "user": 3, "assistant": 4}


def _turns(request: dict, filled: dict) -> list[tuple[str, list[int]]]:
    """``(role, ids)`` of each message, in order: the shared system prompt,
    the session's earlier turns each with its drawn answer, this turn."""
    out = []
    if request["prefix"] >= 0:
        out.append(("system", filled["prefixes"][request["prefix"]]))
    for earlier in request.get("history", ()):
        out += [("user", filled["prompts"][earlier]), ("assistant", filled["answers"][earlier])]
    out.append(("user", filled["prompts"][request["index"]]))
    return out


def messages(request: dict, filled: dict) -> list[dict]:
    return [{"role": role, "content": words(ids)} for role, ids in _turns(request, filled)]


def templated_ids(request: dict, filled: dict) -> list[int]:
    """What the preprocessor must hand the engine for this request."""
    ids = [0]
    for role, content in _turns(request, filled):
        ids += [MARKS[role], *content, 5]
    return ids + [4]
