"""Tiers (the Tier-2 swarm program): device milliseconds per launch of
the ``jit_immsched_swarm_batch`` and ``jit_immsched_swarm`` XLA modules
in the traced window. None when neither ran."""
from chipbench import program_trace


def read(ctx):
    return program_trace.ms_per_launch(ctx, program_trace.SWARM_MODULES)
