"""A completed block is hashed once (ISSUE 58): ``publish_stored`` chains
from the last published hash, and what it leaves behind (a sequence's
``published_hashes``, the ``stored`` events, the reuse registry) is what
hashing the whole token list on every call left (the parent's allocator,
kept below as the oracle, with its own copy of the byte layout so that a
change to ``hashing.py`` cannot move both sides).  ``Sequence.last_token_id``
and ``Sequence.tokens`` read what ``all_token_ids`` held without joining it."""

import asyncio
import random

import pytest
import xxhash

from dynamo_tpu.engine.kv_manager import BlockAllocator
from dynamo_tpu.engine.sequence import Sequence, TokenView
from dynamo_tpu.llm.kv_router.hashing import compute_block_hashes
from dynamo_tpu.llm.mocker import MockerConfig, MockerEngine
from dynamo_tpu.llm.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.runtime.engine import Context

BLOCK_SIZES = [8, 16, 32]
CASES = ["block_at_a_time", "chunked_prefill", "prefix_matched_start",
         "cleared_in_mid_sequence", "ragged_length"]


def whole(tokens, bs):
    """The parent's ``compute_block_hashes``: every full block, from block 0."""
    out, parent = [], 0
    for start in range(0, len(tokens) - len(tokens) % bs, bs):
        data = parent.to_bytes(8, "little") + b"".join(
            t.to_bytes(4, "little", signed=False) for t in tokens[start:start + bs])
        parent = xxhash.xxh3_64(data, seed=1337).intdigest()
        out.append(parent)
    return out


class Oracle:
    """The parent's ``publish_stored`` for one sequence: hash the whole list,
    keep what is past the published ones."""

    def __init__(self, bs, published=()):
        self.bs, self.published, self.events = bs, list(published), []

    def publish(self, tokens):
        hashes = whole(tokens, self.bs)
        new = hashes[len(self.published):]
        if not new:
            return
        parent = self.published[-1] if self.published else None
        self.published = hashes
        self.events.append((new, parent, len(new) * self.bs))


def tokens_of(rng, n):
    return [rng.randrange(0, 2**32) if i % 7 == 0 else rng.randrange(0, 152_000) for i in range(n)]


def stored(events):
    return [(e.block_hashes, e.parent_hash, e.token_count) for e in events if e.kind == "stored"]


def test_a_blocks_hash_is_what_it_was():
    """Two vectors taken on the parent commit (seed, ``parent || tokens``
    bytes and width unmoved), and the chain continued equals the whole."""
    assert compute_block_hashes(list(range(1, 20)), 8) == [5364441795342846830, 3751892901470032822]
    assert compute_block_hashes([151643, 0, 4294967295, 7] * 4, 16) == [15107710252985767507]
    tokens = tokens_of(random.Random(5), 100)
    full = compute_block_hashes(tokens, 8)
    assert full == whole(tokens, 8)
    for have in (0, 1, 5, 12):
        assert compute_block_hashes(tokens, 8, full[:have]) == full[have:]
    assert compute_block_hashes(tokens[:30], 8, full) == []      # fewer tokens than published: nothing


@pytest.mark.parametrize("caching", [True, False], ids=["prefix_caching", "no_reuse"])
@pytest.mark.parametrize("bs", BLOCK_SIZES)
@pytest.mark.parametrize("case", CASES)
def test_the_chain_equals_the_whole(case, bs, caching):
    rng = random.Random(f"{case}/{bs}")     # str seeds hash the same in every worker
    events = []
    alloc = BlockAllocator(512, bs, event_sink=events.append, enable_prefix_caching=caching)
    prompt = tokens_of(rng, 5 * bs + rng.randrange(1, bs))
    answer = tokens_of(rng, 4 * bs + 3)
    want = Oracle(bs)
    registry = set()

    def admit(seq_id, prompt_tokens):
        assert alloc.allocate_sequence(seq_id, len(prompt_tokens), token_ids=prompt_tokens) is not None

    def decode(seq_id, prompt_tokens, oracle, until=None):
        """A token a step; the lane publishes when it has just filled a block."""
        seq = Sequence(seq_id, PreprocessedRequest(token_ids=prompt_tokens))
        for n, tok in enumerate(answer[:until], 1):
            assert alloc.append_slot(seq_id, seq.context_len + 1) is not None
            seq.output_ids.append(tok)
            if seq.context_len % bs == 0:
                alloc.publish_stored(seq_id, seq.tokens)
                oracle.publish(seq.all_token_ids)
            if case == "cleared_in_mid_sequence" and n == 2 * bs:
                alloc.clear_published()
                oracle.published = []
                registry.clear()
        return seq

    if case == "chunked_prefill":
        admit("s", prompt)
        chunk = bs + 3                       # a chunk ends inside a block
        for end in list(range(chunk, len(prompt), chunk)) + [len(prompt)]:
            alloc.publish_stored("s", prompt[:end])
            want.publish(prompt[:end])
        seq = decode("s", prompt, want)
    elif case == "prefix_matched_start":
        first = Oracle(bs)
        admit("first", prompt)
        alloc.publish_stored("first", prompt)
        first.publish(prompt)
        registry.update(first.published)
        shared = prompt[:3 * bs + 2] + tokens_of(rng, 2 * bs)
        admit("s", shared)
        matched = list(alloc._sequences["s"].published_hashes)
        assert matched == (whole(prompt, bs)[:3] if caching else [])
        want = Oracle(bs, matched)
        alloc.publish_stored("s", shared)
        want.publish(shared)
        seq = decode("s", shared, want)
        want.events = first.events + want.events
    elif case == "ragged_length":
        admit("s", prompt)
        alloc.publish_stored("s", prompt)
        want.publish(prompt)
        seq = decode("s", prompt, want, until=bs + bs // 2)   # ends inside a block
        alloc.publish_stored("s", seq.tokens)                  # nothing new to say
        want.publish(seq.all_token_ids)
    else:
        admit("s", prompt)
        alloc.publish_stored("s", prompt)
        want.publish(prompt)
        seq = decode("s", prompt, want)

    everything = seq.all_token_ids
    got = alloc._sequences["s"].published_hashes
    if case == "cleared_in_mid_sequence":
        # hashed from block 0 again at the next publish after the flush
        from_zero = [new for new, parent, _ in want.events if parent is None]
        assert [len(new) for new in from_zero] == [5, 8]
    assert got == want.published == whole(everything, bs)[:len(got)]
    assert len(got) == len(everything) // bs
    assert stored(events) == want.events
    registry.update(want.published)
    assert set(alloc._hash_to_block) == (registry if caching else set())
    assert alloc.publish_blocks_hashed_total == alloc.publish_blocks_stored_total
    assert alloc.publish_blocks_stored_total == sum(len(e[0]) for e in want.events)


@pytest.mark.parametrize("lanes,steps", [(1, 40), (4, 70), (9, 33)])
async def test_the_mock_engine_hashes_a_block_once_by_count(lanes, steps):
    """N decode steps of L lanes on the mock engine (the allocator's own
    method, as the JAX engine runs it): every block of every lane is hashed
    once, whatever the context behind it, and is in one stored event."""
    events = []
    engine = MockerEngine(MockerConfig(max_batch_size=lanes, speedup=1e5), event_sink=events.append)
    engine.start()
    prompts = [[(7 * lane + i) % 1000 for i in range(20 + 11 * lane)] for lane in range(lanes)]

    async def drain(prompt):
        request = PreprocessedRequest(
            token_ids=prompt, stop=StopConditions(max_tokens=steps, ignore_eos=True))
        return [item async for item in await engine.generate(Context(request.to_wire()))]

    try:
        await asyncio.wait_for(asyncio.gather(*map(drain, prompts)), timeout=30.0)
        stats = engine.stats()
    finally:
        engine.stop()
    # the last token ends the sequence before its block is published
    blocks = sum((len(p) + steps - 1) // 16 for p in prompts)
    assert stats["kv_publish_blocks_hashed_total"] == stats["kv_publish_blocks_stored_total"] == blocks
    assert sum(len(e.block_hashes) for e in events if e.kind == "stored") == blocks


@pytest.mark.parametrize("prompt_len,answer_len", [(1, 0), (5, 0), (5, 1), (5, 40), (16, 16)])
def test_a_view_and_a_last_token_read_what_the_joined_list_held(prompt_len, answer_len):
    rng = random.Random(prompt_len * 100 + answer_len)
    seq = Sequence("s", PreprocessedRequest(token_ids=tokens_of(rng, prompt_len)))
    assert seq.last_token_id == seq.all_token_ids[-1]          # before the first generated token
    for tok in tokens_of(rng, answer_len):
        seq.output_ids.append(tok)
        assert seq.last_token_id == seq.all_token_ids[-1] == tok
    view, joined = seq.tokens, seq.all_token_ids
    assert isinstance(view, TokenView) and len(view) == len(joined) == seq.context_len
    for start in range(len(joined) + 1):
        for stop in range(start, len(joined) + 2):
            assert view[start:stop] == joined[start:stop]
