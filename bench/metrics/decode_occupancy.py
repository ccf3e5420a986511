"""Mean live slots per fused decode step over the batch slots, in %: the
tokens decoded for the window's requests over slots times the scan steps
the trace's fused decode programs ran."""

from bench import work


def read(ctx):
    steps = work.decode_steps(ctx)
    if steps is None:
        return None
    _, _, tokens = work.decode(ctx)
    return 100.0 * tokens / (ctx.serving["batch_slots"] * steps[1])
