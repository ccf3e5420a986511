"""Device idle time per engine step under the program's own spans, in ms:
the idle seconds of the window and its drain whose innermost host span is
an ``engine.*`` or ``hsa.*`` one (not the benchmark's ``bench.*``), over
the engine steps the driver took."""

from bench import devtrace, work


def read(ctx):
    idle = None if ctx.reduced is None else devtrace.program_idle(ctx.reduced)
    steps = work.engine_steps(ctx)
    return None if idle is None or not steps else 1e3 * idle / steps
