"""Share of its roofline that the fused decode reaches, in %: the least
time the chip could take for the decode work of the window (the larger of
its FLOPs over peak FLOP/s and its bytes over peak bandwidth: the weights
once per scan step, the K/V of the live lengths) over the device time of
the fused decode programs."""

from bench import work


def read(ctx):
    steps = work.decode_steps(ctx)
    if steps is None:
        return None
    flops, kv_bytes, _ = work.decode(ctx)
    nbytes = steps[1] * ctx.shapes.weight_bytes + kv_bytes
    least = max(flops / ctx.peak_flops, nbytes / ctx.peak_bw)
    return 100.0 * least / steps[2]
