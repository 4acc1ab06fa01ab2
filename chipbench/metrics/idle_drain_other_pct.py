"""Front end (AsyncServiceFrontEnd._drain): share of the traced window
(%) in which the device idled inside an ``immsched.drain`` span with no
child span open (intake, store probes, Tier-1 lookups, Python between
the steps). One of the five program-span shares that split
``device_idle_pct``. None when the trace holds no program span."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_pct(ctx, "immsched.drain")
