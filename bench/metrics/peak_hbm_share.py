"""Peak device memory in use over the device's limit, in % (JAX's
``memory_stats``)."""


def read(ctx):
    limit = ctx.memory.get("bytes_limit")
    peak = ctx.memory.get("peak_bytes_in_use")
    return None if not limit or peak is None else 100.0 * peak / limit
