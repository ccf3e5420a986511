"""Model FLOPs utilization of the whole serving span, in %: the FLOPs the
window's requests required (prefill of unshared rows and every decoded
token, no padding), over the span from the window's start to the end of
its drain times the chip's peak.  It names no program, so it survives a
refactor of the step."""

from bench import work


def read(ctx):
    pre = work.prefill(ctx)
    if pre is None:
        return None
    return 100.0 * (pre[0] + work.decode(ctx)[0]) / (work.traced_span(ctx) * ctx.peak_flops)
