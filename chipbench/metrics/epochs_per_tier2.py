"""Tiers: swarm epochs run per problem the swarm served in the window,
the change of ``epochs_run`` over the change of ``batch_problems``."""


def read(ctx):
    problems = ctx.delta.get("batch_problems", 0)
    if problems <= 0:
        return None
    return ctx.delta["epochs_run"] / problems
