"""Traffic is a pure function of the mix, the rate, the window and the seed.
The loops over mixes run on the repository's and on those the next PR adds
(``trees.py``)."""

import hashlib
import json

import pytest

from benchmark import traffic

from .trees import ROOT, TREES, mixes_of

MIXES = [pytest.param(tree, path, id=f"{tree}-{path.stem}") for tree in TREES for path in mixes_of(tree)]
SESSIONS = [case for case in MIXES if "sessions" in json.loads(case.values[1].read_text())]


def mix_of(path, tree="real", roots=None):
    """The mix in ``path`` as the tree holds it (the copy's own file)."""
    if roots is not None:
        path = roots[tree] / "benchmark" / "traffic" / path.name
    return json.loads(path.read_text())


def turn_lengths(mix):
    """(least, most) new tokens of a turn: the mix's prompts, or in a mix of
    sessions a first turn's and a later turn's together."""
    specs = [mix["sessions"][k] for k in ("first_prompt_tokens", "turn_tokens")] \
        if "sessions" in mix else [mix["prompt_tokens"]]
    return min(s["min"] for s in specs), max(s["max"] for s in specs)


@pytest.mark.parametrize("tree,path", MIXES)
def test_schedule_repeats_and_ignores_the_seed(tree, path, roots):
    mix = mix_of(path, tree, roots)
    a = traffic.schedule(mix, 2.0, 30.0)
    b = traffic.schedule(mix, 2.0, 30.0)
    assert a == b and len(a) > 20
    dues = [r["due"] for r in a]
    assert dues == sorted(dues)
    inside = [r for r in a if not r["probe"]]
    assert -mix["lead_in_s"] <= dues[0] and inside[-1]["due"] < 30.0
    probes = [r for r in a if r["probe"]]
    assert len(probes) == mix["probes"]["requests"] and all(r["due"] >= 30.0 for r in probes)
    assert a[-len(probes):] == probes


@pytest.mark.parametrize("tree,path", MIXES)
def test_lengths_stay_inside_their_clips(tree, path, roots):
    mix = mix_of(path, tree, roots)
    plan = traffic.schedule(mix, 5.0, 40.0)
    clips = {"prompt_len": turn_lengths(mix),
             "output_len": (mix["output_tokens"]["min"], mix["output_tokens"]["max"])}
    for field, (lo, hi) in clips.items():
        got = [r[field] for r in plan]
        assert lo <= min(got) and max(got) <= hi
        assert len(set(got)) > 5  # a distribution, not one length
    if mix.get("shared_prefix"):
        # requests behind a shared prefix (sessions, where a mix has them:
        # every turn of one keeps its prefix): the mix's own share of them
        units = {r.get("session", r["index"]): r["prefix"] for r in plan}
        share = sum(p >= 0 for p in units.values()) / len(units)
        assert abs(share - mix["shared_prefix"]["share"]) < 0.2
        assert {r["prefix"] for r in plan} <= set(range(-1, mix["shared_prefix"]["count"]))
    else:
        assert all(r["prefix"] == -1 for r in plan)


@pytest.mark.parametrize("tree,path", MIXES)
def test_same_seed_same_ids_other_seed_other_ids(tree, path, roots):
    mix = mix_of(path, tree, roots)
    plan = traffic.schedule(mix, 2.0, 10.0)
    big = 2**31 + 12345
    a, b = traffic.fill(plan, mix, 32000, big), traffic.fill(plan, mix, 32000, big)
    c = traffic.fill(plan, mix, 32000, big + 1)
    assert a == b and a["prompts"] != c["prompts"]
    for r in plan:
        ids = a["prompts"][r["index"]]
        assert len(ids) == r["prompt_len"]
        assert min(ids) >= traffic.RESERVED and max(ids) < 32000


def test_rate_scales_the_number_of_requests_not_their_sizes():
    mix = mix_of(ROOT / "benchmark" / "traffic" / "chat.json")
    slow, fast = traffic.schedule(mix, 1.0, 40.0), traffic.schedule(mix, 4.0, 40.0)
    assert 3.0 < len(fast) / len(slow) < 5.0
    assert [r["prompt_len"] for r in slow] == [r["prompt_len"] for r in fast][: len(slow)]


def test_template_ids_match_what_the_messages_say():
    mix = {"shared_prefix": {"share": 1.0, "count": 1, "tokens": 3}}
    plan = [{"index": 0, "due": 0.0, "prompt_len": 2, "output_len": 1, "prefix": 0, "probe": 0},
            {"index": 1, "due": 0.1, "prompt_len": 2, "output_len": 1, "prefix": -1, "probe": 0}]
    filled = traffic.fill(plan, mix, 100, 5)
    p, u0, u1 = filled["prefixes"][0], filled["prompts"][0], filled["prompts"][1]
    assert traffic.templated_ids(plan[0], filled) == [0, 2, *p, 5, 3, *u0, 5, 4]
    assert traffic.templated_ids(plan[1], filled) == [0, 3, *u1, 5, 4]
    msgs = traffic.messages(plan[0], filled)
    assert [m["role"] for m in msgs] == ["system", "user"]
    assert traffic.ids_of(msgs[0]["content"]) == p
    assert traffic.ids_of(traffic.words([7, 8, 9])) == [7, 8, 9]


def test_the_template_renders_to_those_ids():
    jinja2 = pytest.importorskip("jinja2")
    text = jinja2.Template(traffic.CHAT_TEMPLATE).render(
        messages=[{"role": "system", "content": "t9 t10"}, {"role": "user", "content": "t11"}],
        add_generation_prompt=True)
    assert traffic.ids_of(text) == [0, 2, 9, 10, 5, 3, 11, 5, 4]


def test_a_backlog_is_due_in_order_from_the_lead_ins_first_instant():
    mix = mix_of(ROOT / "benchmark" / "traffic" / "long-prompt.json")
    n, gap = mix["backlog"]["requests"], mix["backlog"]["spacing_s"]
    plan = traffic.schedule(mix, 0.6, 51.0)
    head = [r["due"] + mix["lead_in_s"] for r in plan[:n]]
    assert head == pytest.approx([k * gap for k in range(n)])
    plain = traffic.schedule({k: v for k, v in mix.items() if k != "backlog"}, 0.6, 51.0)
    # the arrivals are the plain mix's, starting after the backlog's last
    assert plan[n]["due"] - plain[0]["due"] == pytest.approx(n * gap)
    # the same sizes in the same order, whoever is due first
    assert [r["prompt_len"] for r in plan][:30] == [r["prompt_len"] for r in plain][:30]
    # no two requests so close that their order in the queue is a toss-up
    dues = [r["due"] for r in plan]
    assert min(b - a for a, b in zip(dues, dues[1:])) > 0.01


# -- the mixes that exist schedule and fill as on PR 29's tree ------------------

def digest(obj):
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]


# recorded on the parent commit (023cb16) with this very function: each cell's
# mix at the cell's rate over the 51 s window, its plan, and for seeds 7 and
# 2**31 + 12345 the filled ids, the templated ids and the messages
AS_ON_THE_PARENT = [
    ("chat", 2.0, 151936, 120, "7e9f0b1647bbc8f3",
     [("d8b84eec36b5dd62", "079aadf5079dfe31", "dd6290853343780f"),
      ("36351df9e93ca10c", "c0d244c126812a12", "5d600ae85c0ca44f")]),
    ("long-prompt", 1.57, 32000, 140, "26ccbb9c7bbef45d",
     [("de18dc5b31c7e3bb", "157ae469f299ac87", "c9ca1b4aa113e132"),
      ("0ec3109b5700eede", "803c97026f200cc0", "fd4d6c76219144e5")]),
    ("long-prompt", 1.18, 151936, 124, "2b00e8a545825e52",
     [("f00467a492a4793d", "0e18af24463b72c0", "84ca09d946827bde"),
      ("4257581bdf1aadca", "d500e3108edbfe64", "003a04d3f79f38a7")]),
]


@pytest.mark.parametrize("name,rate,vocab,count,plan_digest,by_seed", AS_ON_THE_PARENT,
                         ids=[f"{row[0]}-{row[1]}" for row in AS_ON_THE_PARENT])
def test_a_mix_without_sessions_schedules_and_fills_bit_for_bit_as_before(
        name, rate, vocab, count, plan_digest, by_seed):
    mix = mix_of(ROOT / "benchmark" / "traffic" / f"{name}.json")
    plan = traffic.schedule(mix, rate, 51.0)
    assert len(plan) == count and digest(plan) == plan_digest
    assert set(plan[0]) == {"index", "due", "prompt_len", "output_len", "prefix", "probe"}
    for seed, want in zip((7, 2**31 + 12345), by_seed):
        filled = traffic.fill(plan, mix, vocab, seed)
        assert set(filled) == {"prefixes", "prompts"}
        assert (digest(filled), digest([traffic.templated_ids(r, filled) for r in plan]),
                digest([traffic.messages(r, filled) for r in plan])) == want


# -- sessions -------------------------------------------------------------------

def by_session(plan):
    out = {}
    for r in plan:
        out.setdefault(r["session"], []).append(r)
    return out


@pytest.mark.parametrize("tree,path", SESSIONS)
def test_a_turn_resends_the_turn_before_it_and_its_drawn_answer_token_for_token(tree, path, roots):
    mix = mix_of(path, tree, roots)
    plan = traffic.schedule(mix, 3.0, 30.0)
    filled = traffic.fill(plan, mix, 32000, 11)
    sessions = by_session(plan)
    assert max(len(turns) for turns in sessions.values()) >= 3
    for turns in sessions.values():
        assert [r["turn"] for r in turns] == list(range(len(turns)))
        assert len({r["prefix"] for r in turns}) == 1
        for before, after in zip(turns, turns[1:]):
            assert after["history"] == before["history"] + [before["index"]]
            sent, answer = traffic.templated_ids(before, filled), filled["answers"][before["index"]]
            assert len(answer) == before["answer_len"]
            again = traffic.templated_ids(after, filled)
            # ... t4 <answer> t5 t3 <the new words> t5 t4
            assert again[: len(sent) + len(answer)] == sent + answer
            assert again[len(sent) + len(answer):] == [5, 3, *filled["prompts"][after["index"]], 5, 4]
            assert len(filled["prompts"][after["index"]]) == after["prompt_len"]


@pytest.mark.parametrize("tree,path", SESSIONS)
def test_sessions_keep_their_count_their_turns_and_their_pauses(tree, path, roots):
    mix = mix_of(path, tree, roots)
    spec = mix["sessions"]
    plan = traffic.schedule(mix, 2.0, 51.0)
    sessions = by_session(plan)
    assert all(len(turns) <= spec["turns"]["max"] for turns in sessions.values())
    assert any(len(turns) >= spec["turns"]["min"] for turns in sessions.values())
    gaps = [b["due"] - a["due"] for turns in sessions.values() for a, b in zip(turns, turns[1:])]
    assert min(gaps) >= spec["think_s"]
    # no more than ``count`` are between their first turn and their last at
    # once, unless none of them had rested when a request came due
    live = [sum(turns[0]["due"] <= r["due"] <= turns[-1]["due"] for turns in sessions.values())
            for r in plan]
    assert max(live) <= spec["count"] + 1 and sorted(live)[len(live) // 2] >= spec["count"] // 2
    # the first requests each open a session of their own
    assert [r["turn"] for r in plan[: spec["count"]]] == [0] * spec["count"]


@pytest.mark.parametrize("tree,path", SESSIONS)
def test_the_seed_owns_the_ids_and_nothing_of_the_plan(tree, path, roots):
    mix = mix_of(path, tree, roots)
    plan = traffic.schedule(mix, 2.0, 20.0)
    assert plan == traffic.schedule(mix, 2.0, 20.0)
    a, b = traffic.fill(plan, mix, 32000, 2**31 + 7), traffic.fill(plan, mix, 32000, 2**31 + 8)
    assert a == traffic.fill(plan, mix, 32000, 2**31 + 7)
    assert a["prompts"] != b["prompts"] and a["answers"] != b["answers"]
    for r in plan:
        assert len(traffic.templated_ids(r, a)) == len(traffic.templated_ids(r, b))


@pytest.mark.parametrize("tree,path", SESSIONS)
def test_fill_messages_and_templated_ids_agree_on_every_turn(tree, path, roots):
    jinja2 = pytest.importorskip("jinja2")
    template = jinja2.Template(traffic.CHAT_TEMPLATE)
    mix = mix_of(path, tree, roots)
    plan = traffic.schedule(mix, 3.0, 20.0)
    filled = traffic.fill(plan, mix, 32000, 5)
    for r in plan:
        msgs = traffic.messages(r, filled)
        ids = traffic.templated_ids(r, filled)
        roles = [m["role"] for m in msgs]
        assert roles == ["system"] * (r["prefix"] >= 0) + ["user", "assistant"] * r["turn"] + ["user"]
        words = sum(len(traffic.ids_of(m["content"])) for m in msgs)
        assert len(ids) == 1 + words + 2 * len(msgs) + 1
        assert traffic.ids_of(template.render(messages=msgs, add_generation_prompt=True)) == ids


def test_the_shared_prefix_mix_fits_its_context_and_outgrows_the_cache():
    """``traffic/shared-prefix.json`` has no cell yet (PERF.md section 7): the
    next cell-adding PR brings it, its rate and its spreads as files.  What
    the mix promises that cell: 3-6 turns, 1k-3k tokens re-sent a turn, no
    request past ``qwen3-4b``'s served context, more history alive than its
    18,432 cached tokens hold."""
    mix = mix_of(ROOT / "benchmark" / "traffic" / "shared-prefix.json")
    served = json.loads((ROOT / "benchmark" / "configs" / "qwen3-4b.json").read_text())["serving"]
    context = served["args"][served["args"].index("--context-length") + 1]
    spec = mix["sessions"]
    assert (spec["turns"]["min"], spec["turns"]["max"]) == (3, 6) and "shared_prefix" not in mix
    worst = (1 + spec["first_prompt_tokens"]["max"] + 2 + 1 + (spec["turns"]["max"] - 1) * (
        spec["answer_tokens_in_history"]["max"] + spec["turn_tokens"]["max"] + 3)
        + mix["output_tokens"]["max"])
    assert worst <= context
    plan = traffic.schedule(mix, 2.0, 51.0)
    filled = traffic.fill(plan, mix, 151936, 3)
    sent = {r["index"]: len(traffic.templated_ids(r, filled)) for r in plan}
    resent = sorted(sent[r["index"]] - r["prompt_len"] for r in plan if r["turn"])
    assert len(resent) > len(plan) // 2                       # most requests are later turns
    assert 1000 <= resent[len(resent) // 20] and resent[-1] <= 3000
    alive = [max(sent[r["index"]] for r in turns) for turns in by_session(plan).values()]
    assert spec["count"] * sorted(alive)[len(alive) // 2] > 1.25 * served["kv_tokens"]
