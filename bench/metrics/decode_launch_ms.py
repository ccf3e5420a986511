"""Device time per fused decode launch, in ms, from the trace."""

from bench import work


def read(ctx):
    steps = work.decode_steps(ctx)
    return None if steps is None else 1e3 * steps[2] / steps[0]
