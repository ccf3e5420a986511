"""The prefill part of ``mfu``, in %: FLOPs of the window's unshared
prompt rows over the serving span times peak."""

from bench import work


def read(ctx):
    pre = work.prefill(ctx)
    if pre is None:
        return None
    return 100.0 * pre[0] / (work.traced_span(ctx) * ctx.peak_flops)
