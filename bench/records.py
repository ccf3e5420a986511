"""End-to-end metrics from the window's request records.

Every number here is taken over all the requests of the window, on the
engine's host clock (``time.perf_counter``, stamped after each step has
synced its tokens to the host):

* ``ttft_p95_s``: 95th percentile over all requests due in the window of
  first token minus due time;
* ``tpot_p95_ms``: 95th percentile over the same requests of
  (finish - first token) / (generated - 1);
* ``output_tok_per_s``: tokens generated inside the window, divided by the
  window's seconds.  A request's first token counts at its first-token
  time, and its other tokens are spread evenly over its decode, from the
  first token to its finish (the engine reports no per-token times).

A request that never completed (failed, or still running when the drain
limit ran out) counts as missing every limit: its TTFT and TPOT are the
whole time from its due time to the end of the drain.
"""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def ttfts(records, drain_end: float) -> list[float]:
    return [(r.first_token if r.done else drain_end) - r.due for r in records]


def tpots(records, drain_end: float) -> list[float]:
    out = []
    for r in records:
        if not r.done:
            out.append(drain_end - r.due)
        elif len(r.generated) > 1:
            out.append((r.finish - r.first_token) / (len(r.generated) - 1))
    return out


def window_tokens(records, end: float) -> float:
    """Tokens generated up to ``end`` by the completed requests."""
    total = 0.0
    for r in records:
        if not r.done or r.first_token > end:
            continue
        total += 1
        rest = len(r.generated) - 1
        if rest and r.finish > r.first_token:
            total += rest * min(1.0, (end - r.first_token) / (r.finish - r.first_token))
        elif rest:
            total += rest
    return total


def end_to_end(window) -> dict[str, float]:
    recs = window.records
    return {
        "ttft_p95_s": percentile(ttfts(recs, window.drain_end), 0.95),
        "tpot_p95_ms": 1e3 * percentile(tpots(recs, window.drain_end), 0.95),
        "output_tok_per_s": window_tokens(recs, window.start + window.seconds)
        / window.seconds,
    }


def tails(window) -> dict[str, float]:
    """Percentiles beside the ones the benchmark reports, for the log."""
    t = ttfts(window.records, window.drain_end)
    p = tpots(window.records, window.drain_end)
    out = {}
    for q in (0.5, 0.75, 0.9, 0.95, 0.99):
        out[f"ttft_p{round(100 * q)}_s"] = percentile(t, q)
        out[f"tpot_p{round(100 * q)}_ms"] = 1e3 * percentile(p, q)
    return out


def lateness(records) -> list[float]:
    """How late the driver submitted each request after its due time."""
    return [r.submitted - r.due for r in records]
