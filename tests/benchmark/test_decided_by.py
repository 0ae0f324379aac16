"""WHICH reference row decided a served token is the reference module's to
say (``decided_by``, ``benchmark/README.md``), teacher-forced on the path the
server reports (``decided_at`` on the wire, ``served_passes`` in the job).
Held on the next tree's ``blockdiff-toy``, a decoder that fixes a block's
tokens pass by pass, instantiated here on the CPU in float32:

- (a) its own generation, read along its own path, reads no gap at all;
- (b) control: the same record with the pass numbers swapped inside each
  block reads a wide one (the check binds the path);
- (c) control: the same record read as next-token generation, by a copy of
  the module without ``decided_by``, fails the same limit;
- (d) a path the stated schedule cannot produce is refused by the module;
- (e) a sample without ``served_passes`` fails the reference child;
- (f) the wire: ``client._one`` against a canned stream with and without
  ``decided_at``, and ``pick_sample`` on both;
- (g) a module without ``decided_by`` is handed the rows ``n - 1 + a``.
"""

import asyncio
import json
import time
import types

import numpy as np
import pytest
from aiohttp import ClientSession, web

from benchmark import client, modules, run as runner, traffic
from benchmark.reference import check, llama_like
from benchmark.server import BenchFailure

from .helpers import TINY_QWEN
from .trees import ADDED

TOY = ADDED / "benchmark" / "reference" / "blockdiff_toy.py"
CONFIG = json.loads((ADDED / "benchmark" / "configs" / "blockdiff-toy.json").read_text())
HF, LIMITS = runner.hf_config(CONFIG), CONFIG["limits"]
BLOCK, PER_PASS, MASK = HF["block_length"], HF["tokens_per_pass"], HF["mask_token_id"]
SEED = 11
SIZES = ((21, 18, 0), (30, 23, 0), (16, 16, 20))     # prompt, answer, the probe's first k


@pytest.fixture(scope="module")
def toy():
    return modules.load(TOY)


@pytest.fixture(scope="module")
def samples(toy):
    """What a sound server returns for three prompts that hold the mask id as
    a word: the module's own plain loop, one stream, a pass at a time."""
    rng = np.random.default_rng(5)
    weights = toy.init_weights(HF, SEED)
    out = []
    for index, (n, m, k) in enumerate(SIZES):
        prompt = rng.integers(traffic.RESERVED, HF["vocab_size"], n).tolist()
        prompt[3] = prompt[n - 2] = MASK
        got = toy.generate(weights, HF, prompt, m, top_k=k)
        out.append({"index": index, "prompt_ids": prompt, "served_ids": got["ids"],
                    "served_passes": got["passes"], "served_logprobs": got["logprobs"], "top": got["top"]})
    return out


def job_of(samples, reference=TOY, **more):
    return dict({"hf": HF, "reference": str(reference), "weights_seed": SEED, "samples": samples}, **more)


def blocks_of(sample):
    """``(first, last)`` places in the answer of each block's answer positions."""
    n, end = len(sample["prompt_ids"]), len(sample["prompt_ids"]) + len(sample["served_ids"])
    return [(max(b * BLOCK, n) - n, min((b + 1) * BLOCK, end) - n)
            for b in range(n // BLOCK, -(-end // BLOCK))]


def swapped(sample):
    """The same tokens, each block's passes told in the opposite order."""
    passes = list(sample["served_passes"])
    for lo, hi in blocks_of(sample):
        last = -(-(hi - lo) // PER_PASS) - 1
        passes[lo:hi] = [last - p for p in passes[lo:hi]]
    return dict(sample, served_passes=passes)


def test_a_the_toys_own_generation_reads_no_gap_along_its_own_path(samples):
    for s in samples:      # the schedule as stated: two a pass, the odd one alone, out of order somewhere
        for lo, hi in blocks_of(s):
            assert sorted(s["served_passes"][lo:hi]) == [k // PER_PASS for k in range(hi - lo)]
    assert any(s["served_passes"][lo:hi] != sorted(s["served_passes"][lo:hi])
               for s in samples for lo, hi in blocks_of(s))
    got = check.run(job_of(samples, control="fp8"))
    assert got["tokens"] == sum(m for _, m, _ in SIZES) and got["probed_tokens"] == 16
    assert got["gap_max"] == 0.0 and got["mismatch"] == 0
    assert got["logprob_err_mean"] < 1e-5 and got["topk_err_mean"] < 1e-5
    # the control goes down the same path with its own leaves
    assert got["control_logprob_err_mean"] > LIMITS["logprob_err_mean"]
    assert got["control_topk_err_mean"] > LIMITS["topk_err_mean"]
    ok, _ = runner.compare(got, {"failed": 0, "mismatched": 0}, LIMITS)
    assert ok


def test_b_control_the_pass_numbers_swapped_inside_each_block_read_a_wide_gap(samples):
    got = check.run(job_of([swapped(s) for s in samples]))
    assert got["gap_max"] > LIMITS["gap_max"] == 0.5 and got["mismatch"] > 10
    assert check.run(job_of(samples))["gap_max"] == 0.0        # nothing but the path differs
    assert got["logprob_err_mean"] > LIMITS["logprob_err_mean"]
    ok, lines = runner.compare(got, {"failed": 0, "mismatched": 0}, LIMITS)
    assert not ok and any(line.startswith("compare logit_gap_max") and line.endswith("FAIL") for line in lines)


def test_c_control_read_as_next_token_generation_it_fails_the_same_limit(samples, tmp_path):
    copy = tmp_path / "blockdiff_toy_next_token.py"
    copy.write_text(TOY.read_text().replace("def decided_by(", "def _decided_by("))
    assert not hasattr(modules.load(copy), "decided_by")
    got = check.run(job_of(samples, reference=copy))
    assert got["gap_max"] > LIMITS["gap_max"] and got["mismatch"] > got["tokens"] // 2
    assert check.run(job_of(samples))["gap_max"] == 0.0        # nothing but the fifth function differs
    # and without the pass numbers that copy reads the same: it never asks for them
    bare = [{k: v for k, v in s.items() if k != "served_passes"} for s in samples]
    assert check.run(job_of(bare, reference=copy))["gap_max"] == got["gap_max"]


@pytest.mark.parametrize("fault,match", [
    ("three in one pass", "3 tokens fixed in one pass"),
    ("a pass past the last", "fixed at pass 2"),
    ("a pass before the first", "fixed at pass -1"),
])
def test_d_a_path_the_schedule_cannot_produce_is_refused(samples, fault, match):
    s = samples[2]                      # 16 + 16: whole blocks
    lo, hi = blocks_of(s)[1]
    passes = list(s["served_passes"])
    later = lo + passes[lo:hi].index(1)
    passes[later] = {"three in one pass": 0, "a pass past the last": 2, "a pass before the first": -1}[fault]
    with pytest.raises(ValueError, match=match):
        check.run(job_of([dict(s, served_passes=passes)]))


@pytest.mark.parametrize("passes", [None, "one short"])
def test_e_a_sample_without_served_passes_fails_the_child(samples, tmp_path, passes):
    """A server that did not say how it decoded cannot be ``correct``: the
    child fails, the sample is not skipped."""
    first = dict(samples[0], served_passes=None if passes is None else samples[0]["served_passes"][:-1])
    with pytest.raises(SystemExit, match="decided_at"):
        check.run(job_of([first, samples[1]]))
    if passes is None:                  # and through the harness's own call of the child
        with pytest.raises(BenchFailure, match="reported no decided_at"):
            runner.run_reference(job_of([first, samples[1]]), tmp_path, time.monotonic() + 120.0)


def events_of(sample, decided_at: bool):
    """The stream a server answers with: a chunk a committed block, its
    tokens in order, one ``logprobs.content`` entry a token."""
    out = []
    for lo, hi in blocks_of(sample):
        content = []
        for a in range(lo, hi):
            entry = {"token": f"t{sample['served_ids'][a]}", "logprob": sample["served_logprobs"][a]}
            if decided_at:
                entry["decided_at"] = sample["served_passes"][a]
            if sample["top"]:
                entry["top_logprobs"] = [{"token": f"t{t}", "logprob": v} for t, v in sample["top"][a]]
            content.append(entry)
        out.append({"choices": [{"delta": {"content": " " * bool(lo) + traffic.words(sample["served_ids"][lo:hi])},
                                 "logprobs": {"content": content}, "finish_reason": None}]})
    out[-1]["choices"][0]["finish_reason"] = "length"
    out.append({"choices": [], "usage": {"prompt_tokens": len(sample["prompt_ids"]),
                                         "completion_tokens": len(sample["served_ids"])}})
    return out


def answered(request, filled, events):
    """``client._one`` against a server that answers ``events``: the record,
    and the body the server was sent."""
    bodies = []

    async def handler(req):
        bodies.append(await req.json())
        resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
        await resp.prepare(req)
        for event in events:
            await resp.write(f"data: {json.dumps(event)}\n\n".encode())
        await resp.write(b"data: [DONE]\n\n")
        return resp

    async def go():
        app = web.Application()
        app.router.add_post("/v1/chat/completions", handler)
        site_runner = web.AppRunner(app)
        await site_runner.setup()
        site = web.TCPSite(site_runner, "127.0.0.1", 0)
        await site.start()
        port = site_runner.addresses[0][1]
        record = client.new_record(request, len(traffic.templated_ids(request, filled)))
        try:
            async with ClientSession() as session:
                await client._one(session, f"http://127.0.0.1:{port}/v1/chat/completions", "bench",
                                  request, filled, time.monotonic(), record, time.monotonic() + 30.0)
        finally:
            await site_runner.cleanup()
        return record

    return asyncio.run(go()), bodies[0]


def test_f_the_wire_carries_the_pass_numbers_and_the_sample_hands_them_on(toy):
    weights = toy.init_weights(HF, SEED)
    rng = np.random.default_rng(9)
    plan = [{"index": 0, "due": 0.0, "prompt_len": 13, "output_len": 14, "prefix": -1, "probe": 0},
            {"index": 1, "due": 0.0, "prompt_len": 9, "output_len": 11, "prefix": -1, "probe": 20}]
    filled = {"prefixes": [], "prompts": {r["index"]: rng.integers(traffic.RESERVED, HF["vocab_size"],
                                                                    r["prompt_len"]).tolist() for r in plan}}
    served, records, plain = {}, [], []
    for request in plan:
        prompt = traffic.templated_ids(request, filled)
        got = toy.generate(weights, HF, prompt, request["output_len"], top_k=request["probe"])
        served[request["index"]] = s = {"prompt_ids": prompt, "served_ids": got["ids"], "served_passes": got["passes"],
                                        "served_logprobs": got["logprobs"], "top": got["top"]}
        record, body = answered(request, filled, events_of(s, decided_at=True))
        # what a server is asked is what it was asked before: no key more
        assert set(body) == {"model", "messages", "max_tokens", "temperature", "stream", "stream_options",
                             "ext", "logprobs"} | ({"top_logprobs"} if request["probe"] else set())
        assert record["error"] is None and record["finish"] == "length"
        assert record["ids"] == got["ids"] and record["passes"] == got["passes"]
        assert record["logprobs"] == got["logprobs"]
        assert [k for _, k in record["chunks"]] == [hi - lo for lo, hi in blocks_of(s)]    # a chunk a block
        records.append(record)
        without, _ = answered(request, filled, events_of(s, decided_at=False))
        assert without["passes"] == [] and without["ids"] == got["ids"] and without["logprobs"] == got["logprobs"]
        plain.append(without)
    by_index = {r["index"]: r for r in plan}
    sample = runner.pick_sample(records, by_index, filled, 4, SEED)
    assert [s["index"] for s in sample] == [0, 1]
    for s in sample:
        assert {k: s[k] for k in served[s["index"]]} == served[s["index"]]
    assert sample[0]["top"] is None and len(sample[1]["top"]) == 11
    # from the wire to the comparison: the path the server reported is the one the reference walks
    got = check.run(job_of(sample))
    assert got["gap_max"] == 0.0 and got["mismatch"] == 0 and got["logprob_err_mean"] < 1e-5
    assert got["probed_tokens"] == 11 and got["topk_err_mean"] < 1e-5
    # a server that says nothing of its passes: None, as every served model of the benchmark
    bare = runner.pick_sample(plain, by_index, filled, 4, SEED)
    assert [s["served_passes"] for s in bare] == [None, None]
    assert [{k: v for k, v in s.items() if k != "served_passes"} for s in bare] \
        == [{k: v for k, v in s.items() if k != "served_passes"} for s in sample]
    # and one that says it of some tokens only has not said it
    records[0]["passes"].pop()
    assert runner.pick_sample(records, by_index, filled, 4, SEED)[0]["served_passes"] is None


def test_g_a_module_without_decided_by_is_handed_the_rows_before_each_token(monkeypatch):
    """``llama_like`` on a tiny sample, through a spy on the module: ONE
    trunk a sample over prompt + answer with nothing hidden, and the rows
    handed to ``logits`` are its rows ``n - 1 + a``, ``PAD`` at a time."""
    hf = TINY_QWEN
    rng = np.random.default_rng(3)
    seen = {"hidden": [], "logits": []}

    def hidden(weights, hf, ids):
        x = llama_like.hidden(weights, hf, ids)
        seen["hidden"].append((list(ids), np.asarray(x)))
        return x

    def logits(weights, hf, x):
        seen["logits"].append(np.asarray(x))
        return llama_like.logits(weights, hf, x)

    spy = types.SimpleNamespace(init_weights=llama_like.init_weights, quantize=llama_like.quantize,
                                hidden=hidden, logits=logits)
    assert not hasattr(llama_like, "decided_by")
    monkeypatch.setattr(modules, "load", lambda path: spy)
    sizes = ((20, 6), (33, check.PAD + 3))
    samples = [{"index": i, "prompt_ids": rng.integers(8, hf["vocab_size"], n).tolist(),
                "served_ids": rng.integers(8, hf["vocab_size"], m).tolist(),
                "served_passes": [0] * m}                       # ignored: the module does not ask
               for i, (n, m) in enumerate(sizes)]
    got = check.run({"hf": hf, "reference": "the spy", "weights_seed": SEED, "samples": samples})
    assert got["tokens"] == sum(m for _, m in sizes)
    length = 2 * check.PAD                                      # 33 + 259 to the next multiple
    assert [len(ids) for ids, _ in seen["hidden"]] == [length, length]
    calls = iter(seen["logits"])
    for s, (ids, x) in zip(samples, seen["hidden"]):
        n, m = len(s["prompt_ids"]), len(s["served_ids"])
        assert ids == s["prompt_ids"] + s["served_ids"] + [0] * (length - n - m)
        for a in range(0, m, check.PAD):
            rows, real = next(calls), min(check.PAD, m - a)
            assert rows.shape == (check.PAD, hf["hidden_size"])
            assert np.array_equal(rows[:real], x[n - 1 + a:n - 1 + a + real])
    assert next(calls, None) is None
