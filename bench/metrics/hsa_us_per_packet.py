"""Host time the HSA runtime adds to each packet, in microseconds: submit,
grant and the producer's wait, less the execution they wait on, over the
packets of the window (the queue's ``OverheadLedger``)."""

from repro.core import ledger as L


def read(ctx):
    n = ctx.hsa[L.DISPATCH_SUBMIT][0]
    if not n:
        return None
    total = sum(ctx.hsa[c][1] for c in (L.DISPATCH_SUBMIT, L.DISPATCH_GRANT,
                                          L.DISPATCH_WAIT))
    return 1e6 * (total - ctx.hsa[L.EXEC][1]) / n
