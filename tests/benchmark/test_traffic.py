"""Traffic is a pure function of the mix, the rate, the window and the seed."""

import json
from pathlib import Path

import pytest

from benchmark import traffic

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted((ROOT / "benchmark" / "traffic").glob("*.json"))


def mix_of(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_schedule_repeats_and_ignores_the_seed(path):
    mix = mix_of(path)
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


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_lengths_stay_inside_their_clips(path):
    mix = mix_of(path)
    plan = traffic.schedule(mix, 5.0, 40.0)
    for key, field in (("prompt_tokens", "prompt_len"), ("output_tokens", "output_len")):
        lo, hi = mix[key]["min"], mix[key]["max"]
        got = [r[field] for r in plan]
        assert lo <= min(got) and max(got) <= hi
        assert len(set(got)) > 5  # a distribution, not one length
    if mix.get("shared_prefix"):
        share = sum(r["prefix"] >= 0 for r in plan) / len(plan)
        assert 0.3 < share < 0.7
        assert {r["prefix"] for r in plan} <= set(range(-1, mix["shared_prefix"]["count"]))
    else:
        assert all(r["prefix"] == -1 for r in plan)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_ids_other_seed_other_ids(path):
    mix = mix_of(path)
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
