"""Drain (MatcherService.drain): host time blocked in device-to-host
fetches per drain round over the window (ms): the change of
``host_sync_wall_s`` over the change of ``drains``."""


def read(ctx):
    drains = ctx.delta.get("drains", 0)
    if drains <= 0:
        return None
    return 1e3 * ctx.delta["host_sync_wall_s"] / drains
