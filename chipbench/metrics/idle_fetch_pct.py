"""Drain fetch (MatcherService._sync_fetch): share of the traced window
(%) in which the device idled while the host was blocked in
``immsched.fetch``, on transfer and sync rather than on compute. One of
the five program-span shares that split ``device_idle_pct``. None when
the trace holds no program span."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_pct(ctx, "immsched.fetch")
