"""Helpers the per-layer metric readers share."""


def swarm_problems(ctx):
    """``(n, m, epochs_run)`` of every real problem a Tier-2 swarm launch
    answered inside the window: the window's tile count and the request's
    free-engine count, never the padded bucket."""
    out = []
    for r in ctx.records:
        res = r.result
        if res is None or r.done is None or r.done > ctx.end:
            continue
        if getattr(res, "tier", None) == 2 and res.epochs_run > 0:
            out.append((ctx.windows[r.problem.req.window].n,
                        int(r.problem.req.free.sum()), int(res.epochs_run)))
    return out
