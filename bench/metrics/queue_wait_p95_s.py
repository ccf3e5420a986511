"""Nearest-rank p95 of queue wait over the window's requests, in s: from
a request's due time to its admission (``Request.admit_t``, when a slot
took it), or to the end of the drain for one never admitted."""

from bench.records import percentile


def read(ctx):
    w = ctx.window
    if all(r.admit is None for r in w.records):
        return None
    return percentile([(w.drain_end if r.admit is None else r.admit) - r.due
                       for r in w.records], 0.95)
