"""Device time of the fused decode's attention per launch, in ms: the own
time of the operations under the ``attn.kv_write`` and ``attn.core``
scopes of the fused decode programs (``Reduced.scopes``), over their
launches in the window."""

import re

from bench import work

SCOPES = ("attn.kv_write", "attn.core")


def read(ctx):
    steps = work.decode_steps(ctx)
    if steps is None:
        return None
    fused = [by for name, by in ctx.reduced.scopes.items() if re.search(work.DECODE, name)]
    if not any(s in by for by in fused for s in SCOPES):
        return None
    return 1e3 * sum(by.get(s, 0.0) for by in fused for s in SCOPES) / steps[0]
