"""Share of its roofline that chunked prefill reaches, in %: the least
time the chip could take for the window's prompts (unshared rows only, no
padding; the larger of FLOPs over peak and bytes over bandwidth) over the
device time of the prefill chunk programs."""

from bench import work


def read(ctx):
    t, pre = work.chunk_time(ctx), work.prefill(ctx)
    if t is None or pre is None:
        return None
    least = max(pre[0] / ctx.peak_flops, pre[1] / ctx.peak_bw)
    return 100.0 * least / t[1]
