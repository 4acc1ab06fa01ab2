"""Tiers (the Tier-0/1 revalidation program): device milliseconds per
launch of the ``jit_immsched_revalidate`` XLA module in the traced
window. None when it did not run."""
from chipbench import program_trace


def read(ctx):
    return program_trace.ms_per_launch(ctx, program_trace.REVAL_MODULES)
