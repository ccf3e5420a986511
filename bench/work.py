"""Work the window's requests required, from their shapes (``bench.counts``).

Counted for the requests that completed; each request's shared rows are
the prefix pages the engine attached at its admission (the engine's own
prefix lookups, logged in admission order, which is submission order).
"""

from __future__ import annotations

import re

from bench import counts, devtrace

#: a fused decode program, with its depth K
DECODE = r"decode_fused_k(\d+)"
PREFILL_CHUNK = r"prefill_chunk"


def requests(ctx):
    """``(record, shared_rows)`` of each completed request of the window,
    or ``None`` where the prefix lookups cannot be matched to requests."""
    if ctx.lookups is None:
        return None
    ps = ctx.serving["page_size"]
    recs = sorted(ctx.window.records, key=lambda r: r.uid)
    return [(r, saved * ps) for r, (_, saved) in zip(recs, ctx.lookups) if r.done]


def prefill(ctx):
    """``(flops, bytes)`` of every prompt's unshared rows, or ``None``."""
    reqs = requests(ctx)
    if reqs is None:
        return None
    f = b = 0
    for r, shared in reqs:
        df, db, _ = counts.prefill(ctx.shapes, shared, len(r.spec.prompt),
                                   ctx.serving["prefill_chunk"])
        f, b = f + df, b + db
    return f, b


def decode(ctx):
    """``(flops, kv_bytes, tokens)`` of every decoded token (all but the
    first of each request, which the prefill gives)."""
    f = b = t = 0
    for r in ctx.window.records:
        if r.done:
            df, db = counts.decode(ctx.shapes, len(r.spec.prompt), len(r.generated))
            f, b, t = f + df, b + db, t + len(r.generated) - 1
    return f, b, t


def decode_steps(ctx):
    """``(launches, scan steps, device seconds)`` of the fused decode
    programs in the trace, or ``None`` without a trace."""
    red = ctx.reduced
    if red is None:
        return None
    n = steps = 0
    sec = 0.0
    for name, (k, s) in red.programs.items():
        m = re.search(DECODE, name)
        if m:
            n, steps, sec = n + k, steps + k * int(m.group(1)), sec + s
    return (n, steps, sec) if n else None


def traced_span(ctx) -> float:
    """Seconds from the window's start to the end of its drain."""
    return ctx.window.drain_end - ctx.window.start


def chunk_time(ctx):
    if ctx.reduced is None:
        return None
    n, s = devtrace.launches(ctx.reduced, PREFILL_CHUNK)
    return (n, s) if n else None


def engine_steps(ctx) -> int:
    """Engine steps the driver took in the window and its drain."""
    return ctx.window.steps + ctx.window.drain_steps
