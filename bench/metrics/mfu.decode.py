"""The decode part of ``mfu``, in %: FLOPs of the window's decoded tokens
over the serving span times peak."""

from bench import work


def read(ctx):
    return 100.0 * work.decode(ctx)[0] / (work.traced_span(ctx) * ctx.peak_flops)
