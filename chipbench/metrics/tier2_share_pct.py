"""Tiers (core/pso.py programs): share of the window's answered requests
that went through the Tier-2 swarm (%), the change of ``tier2_checked``
over the requests answered in the window. ``tier2_hits`` would count
only the swarm's found answers and leave out the requests that burned
the whole epoch budget."""


def read(ctx):
    answered = sum(r.done is not None and r.done <= ctx.end
                   for r in ctx.records)
    if answered <= 0:
        return None
    return 100.0 * ctx.delta.get("tier2_checked", 0) / answered
