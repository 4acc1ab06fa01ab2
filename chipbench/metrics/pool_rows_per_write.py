"""Drain (MatcherService.drain): carry-pool rows stored per write-back
program over the window: the change of ``pool_puts`` over the change of
``pool_writes``. It reads how far one write-back program serves several
rows of a launch. A service without the ``pool_writes`` counter reports
nothing."""


def read(ctx):
    writes = ctx.delta.get("pool_writes", 0)
    if writes <= 0:
        return None
    return ctx.delta["pool_puts"] / writes
