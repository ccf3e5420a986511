"""Nearest-rank p95 of prefill time over the window's requests that were
admitted and made their first token, in s: first token minus admission
(``Request.admit_t``)."""

from bench.records import percentile


def read(ctx):
    spans = [r.first_token - r.admit for r in ctx.window.records
             if r.first_token is not None and r.admit is not None]
    return percentile(spans, 0.95) if spans else None
