"""Share of the traced span in which no operation ran on the device, in %
(1 - union of the device's operation intervals over the span)."""


def read(ctx):
    return None if ctx.reduced is None else 100.0 * ctx.reduced.idle_share
