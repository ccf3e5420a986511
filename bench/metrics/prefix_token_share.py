"""Share of the window's prompt tokens that the engine attached from
resident prefix pages instead of prefilling (``prefix_pages_saved`` times
the page size, over the prompt tokens of the window's requests), in %."""


def read(ctx):
    prompt = sum(len(r.spec.prompt) for r in ctx.window.records)
    if not prompt:
        return None
    return 100.0 * ctx.prefix_pages_saved * ctx.serving["page_size"] / prompt
