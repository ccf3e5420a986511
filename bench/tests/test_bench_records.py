"""End-to-end metric arithmetic on synthetic request records."""

from __future__ import annotations

import numpy as np
import pytest

from bench import driver, loadgen, records


def _rec(due, first=None, finish=None, n=0, uid=0):
    spec = loadgen.RequestSpec(uid, np.zeros(4, np.int32), max(n, 1), due=due)
    return driver.Record(spec, due, due, uid, first, finish,
                         list(range(n)) if finish is not None else None)


def test_percentile_is_nearest_rank():
    assert records.percentile([5, 1, 3, 2, 4], 0.5) == 3
    assert records.percentile(list(range(1, 101)), 0.95) == 95
    assert records.percentile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        records.percentile([], 0.5)


def test_ttft_and_tpot_over_all_requests_with_failures_missing():
    recs = [_rec(0.0, 0.5, 1.5, 11), _rec(1.0, 1.2, 2.2, 6), _rec(2.0)]
    # the unfinished request counts its whole wait to the end of the drain
    assert records.ttfts(recs, 10.0) == pytest.approx([0.5, 0.2, 8.0])
    assert records.tpots(recs, 10.0) == pytest.approx([0.1, 0.2, 8.0])


def test_rate_counts_the_tokens_generated_inside_the_window():
    recs = [_rec(0.0, 0.5, 4.0, 10), _rec(1.0, 2.0, 9.9, 20),
            _rec(2.0, 3.0, 11.0, 33), _rec(3.0, 10.5, 12.0, 5), _rec(3.0)]
    w = driver.Window(start=0.0, seconds=10.0, records=recs, drain_end=12.0, steps=0)
    e2e = records.end_to_end(w)
    # the third request made its first token and 7/8 of its other 32 by the
    # close; the fourth began after it; the unfinished one made none known
    assert e2e["output_tok_per_s"] == pytest.approx((10 + 20 + 1 + 28) / 10.0)
    # 5 requests: nearest-rank p95 is the largest, the unfinished one
    assert e2e["ttft_p95_s"] == pytest.approx(12.0 - 3.0)
    assert records.tails(w)["ttft_p50_s"] == pytest.approx(1.0)


def test_single_token_requests_have_no_tpot():
    recs = [_rec(0.0, 0.5, 0.5, 1), _rec(0.0, 0.1, 1.1, 3)]
    assert records.tpots(recs, 5.0) == pytest.approx([0.5])


def test_lateness_is_submit_minus_due():
    r = _rec(1.0)
    r.submitted = 1.25
    assert records.lateness([r]) == pytest.approx([0.25])
