"""Drain apply (MatcherService._apply_*): share of the traced window (%)
in which the device idled while the host's innermost program span was
``immsched.apply`` (result collection, carry stores, pool writes). One
of the five program-span shares that split ``device_idle_pct``. None
when the trace holds no program span."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_pct(ctx, "immsched.apply")
