"""Drain (MatcherService.drain): payload fetched to the host per Tier-2
swarm launch over the window (KiB): the change of ``tier2_fetch_bytes``
over the change of ``tier2_launches``, over 1024. A service without the
``tier2_fetch_bytes`` counter reports nothing."""


def read(ctx):
    launches = ctx.delta.get("tier2_launches", 0)
    if "tier2_fetch_bytes" not in ctx.delta or launches <= 0:
        return None
    return ctx.delta["tier2_fetch_bytes"] / launches / 1024
