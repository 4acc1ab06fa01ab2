"""Kernels (kernels/finish_fused.py): the fused epoch-tail kernel's share
of its roofline (%), counted as for ``epoch_fused_roofline``."""
from chipbench import roofline
from chipbench.metrics_common import swarm_problems


def read(ctx):
    if ctx.trace is None or ctx.trace.kernel_s.get("epoch_finish", 0) <= 0:
        return None
    least = roofline.swarm_least_times(swarm_problems(ctx), ctx.pso,
                                       ctx.peak)["epoch_finish"]
    return 100.0 * least / ctx.trace.kernel_s["epoch_finish"]
