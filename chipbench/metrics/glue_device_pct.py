"""Drain (eager glue): share (%) of the device time of all XLA modules
in the traced window taken by modules other than the tier programs
(``jit_immsched_*``): pool writes, gathers, carry stacks, cold priors
and result slices launched one by one from the host. None when no tier
program ran under its name."""
from chipbench import program_trace


def read(ctx):
    s = program_trace.summary_for(ctx)
    if s is None:
        return None
    tier = sum(v for k, v in s.module_s.items()
               if k.startswith(program_trace.TIER_MODULE))
    total = sum(s.module_s.values())
    if tier <= 0:
        return None
    return 100.0 * (total - tier) / total
