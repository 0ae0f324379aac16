"""Percentiles, rates and spreads on hand-made samples."""

import pytest

from benchmark import arith


def rec(due, chunks, prompt=10, error=None):
    return {"due": due, "chunks": chunks, "prompt_tokens": prompt, "error": error}


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5), ([10], 95, 10.0),
    (list(range(1, 101)), 95, 95.05), ([4, 1, 3, 2], 0, 1.0), ([4, 1, 3, 2], 100, 4.0),
])
def test_percentile(values, q, want):
    assert arith.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_spread_is_the_quartile_distance_over_the_median():
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25, median 3.5
    assert arith.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert arith.spread([100, 100, 100, 100, 100, 100]) == 0.0


def test_ttft_counts_from_the_due_instant_and_a_failure_as_a_miss():
    records = [
        rec(1.0, [(1.5, 1), (1.6, 1)]),            # 500 ms
        rec(2.0, [(2.1, 1)]),                      # 100 ms
        rec(3.0, [], error="HTTP 500"),            # a miss
        rec(4.0, []),                              # never answered: a miss
        rec(-1.0, [(0.2, 1)]),                     # lead-in: not a sample
        rec(10.0, [(10.1, 1)]),                    # due after the window
    ]
    assert sorted(arith.ttfts_ms(records, 10.0, miss_ms=9e3)) == pytest.approx(
        [100.0, 500.0, 9e3, 9e3])


def test_gaps_are_those_that_closed_inside_the_window():
    records = [rec(0.0, [(0.5, 1), (0.6, 1), (0.9, 2)]),
               rec(-2.0, [(-0.1, 1), (0.1, 1)]),      # closes inside: counts
               rec(9.0, [(9.9, 1), (10.2, 1)])]       # closes after: does not
    assert sorted(arith.gaps_ms(records, 10.0)) == pytest.approx([100.0, 200.0, 300.0])


RECORDS = [
    rec(0.0, [(1.0, 1), (2.0, 2)], prompt=100),    # 100 + 3
    rec(-3.0, [(-0.5, 1), (0.5, 1)], prompt=50),   # prompt answered before: 0 + 1
    rec(8.0, [(9.5, 1), (10.5, 1)], prompt=70),    # 70 + 1
    rec(9.0, [(11.0, 1)], prompt=30),              # nothing inside
    rec(5.0, [], prompt=999, error="refused"),     # nothing
]


def test_unsplit_tokens_count_prompts_first_answered_inside_and_tokens_that_arrived_inside():
    assert arith.tokens_unsplit(RECORDS, 10.0) == 100 + 3 + 1 + 70 + 1
    e = arith.end_to_end(RECORDS, 10.0, miss_ms=5e4)
    assert e["tok_per_s_unsplit"] == pytest.approx(17.5)
    assert e["ttft_p95_ms"] > 1e4  # the refused request is in the tail
    assert {"itl_p50_ms", "itl_p95_ms"} <= set(e)


def test_tok_per_s_credits_a_step_across_an_edge_by_the_share_of_it_inside():
    # steps as the client sees them: ... -0.5 | 0.5 | 1.0 | 2.0 | 9.5 | 10.5 | 11.0
    # the step (-0.5, 0.5] is half inside (1 token -> 0.5), (9.5, 10.5] too,
    # the first arrival of all has no step before it and lies outside
    assert arith.tokens_in_window(RECORDS, 10.0) == pytest.approx(101 + 2 + 0.5 + 71 + 0.5)
    assert arith.end_to_end(RECORDS, 10.0, miss_ms=5e4)["tok_per_s"] == pytest.approx(17.5)


@pytest.mark.parametrize("close, inside", [(10.0, 0.0), (10.25, 0.25), (10.9, 0.9), (11.0, 1.0),
                                          (11.004, 1.0), (12.0, 1.0)])
def test_a_prompt_step_that_straddles_the_close_counts_in_proportion(close, inside):
    # a decode lane ticks every 0.1 s, stalls for the 1 s step that carries
    # the 2,000-token prompt, and both answer at 11.0 (4 ms apart)
    lane = rec(0.0, [(t / 10, 1) for t in range(1, 101)] + [(11.004, 1)], prompt=10)
    prompt = rec(5.0, [(11.0, 1)], prompt=2000)
    base = 10 + 100
    got = arith.tokens_in_window([lane, prompt], close)
    assert got == pytest.approx(base + inside * 2002, abs=2002 * 0.005)
    # the unsplit count jumps by the whole prompt at the arrival
    assert arith.tokens_unsplit([lane, prompt], close) == base + (2002 if close >= 11.004 else 2001 if close >= 11.0 else 0)


def test_split_and_unsplit_agree_over_all_time():
    wide = [dict(r, chunks=[(t + 50, n) for t, n in r["chunks"]]) for r in RECORDS]
    assert arith.tokens_in_window(wide, 100.0) == pytest.approx(arith.tokens_unsplit(wide, 100.0))


def test_no_gap_no_itl():
    e = arith.end_to_end([rec(0.0, [(0.5, 1)])], 1.0, miss_ms=1.0)
    assert "itl_p50_ms" not in e and e["ttft_p95_ms"] == pytest.approx(500.0)


FIXED = [dict(rec(-5.0, [(-1.0, 1), (-0.7, 1), (0.2, 1)]), index=0),    # lead-in: -0.7 closes outside
         dict(rec(0.0, [(1.0, 1), (1.1, 1), (9.8, 1), (10.6, 1)]), index=1),   # the last closes in the drain
         dict(rec(1.0, [(2.0, 1), (2.05, 1)]), index=2),
         dict(rec(2.0, [(3.0, 1)]), index=3)]


def test_gaps_of_a_range_of_requests_count_wherever_they_close():
    assert arith.gaps_of_requests_ms(FIXED, 0, 2) == pytest.approx([300.0, 900.0, 100.0, 8700.0, 800.0])
    assert arith.gaps_of_requests_ms(FIXED, 2, 4) == pytest.approx([50.0])
    assert arith.gaps_of_requests_ms(FIXED, 3, 9) == []
    # the window's own set leaves out the lead-in's gap and the drain's
    assert sorted(arith.gaps_ms(FIXED, 10.0)) == pytest.approx([50.0, 100.0, 900.0, 8700.0])


@pytest.mark.parametrize("gap_requests, p95", [
    (None, 7530.0), ([0, 2], 7140.0), ([2, 3], 50.0), ([1, 3], 7515.0)])
def test_a_cells_range_moves_itl_p95_ms_and_nothing_else(gap_requests, p95):
    plain = arith.end_to_end(FIXED, 10.0, miss_ms=5e4)
    got = arith.end_to_end(FIXED, 10.0, 5e4, gap_requests)
    assert got.pop("itl_p95_ms") == pytest.approx(p95)
    assert plain.pop("itl_p95_ms") == plain["itl_p95_window_ms"] == pytest.approx(7530.0)
    assert got == plain          # the median, the window's tail, the rates, the TTFT


def test_a_range_that_holds_no_gap_reports_no_tail():
    e = arith.end_to_end(FIXED, 10.0, 5e4, [3, 9])
    assert "itl_p95_ms" not in e and e["itl_p95_window_ms"] == pytest.approx(7530.0)
