"""Host waits for the device per engine step: the host syncs that the
engine's and the HSA system's ledgers counted in the window and its drain
(``ctx.counters["host_syncs"]``), over the engine steps the driver took."""

from bench import work


def read(ctx):
    steps = work.engine_steps(ctx)
    return ctx.counters["host_syncs"] / steps if steps else None
