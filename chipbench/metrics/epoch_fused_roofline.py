"""Kernels (kernels/epoch_fused.py): the fused inner-loop kernel's share
of its roofline (%): the least time the chip needs for the work of the
window's swarm problems (chipbench/roofline.py, at each problem's real
size and epochs run) over the device time of the kernel's events in the
trace. None when the trace holds no such event."""
from chipbench import roofline
from chipbench.metrics_common import swarm_problems


def read(ctx):
    if ctx.trace is None or ctx.trace.kernel_s.get("epoch_fused", 0) <= 0:
        return None
    least = roofline.swarm_least_times(swarm_problems(ctx), ctx.pso,
                                       ctx.peak)["epoch_fused"]
    return 100.0 * least / ctx.trace.kernel_s["epoch_fused"]
