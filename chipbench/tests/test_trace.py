"""The trace reduction on a small trace recorded on a TPU v5e ("TPU v5
lite"): a 0.4-second window of ``edge.burst_mixed`` traced by
``chipbench/run.py --trace 1`` and committed gzipped. The numbers below
were read from that file once; the reduction must keep giving them."""
import os

import pytest

from chipbench import roofline, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "trace_edge.xplane.pb.gz")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_trace(FIXTURE)


def test_window_and_busy_time(summary):
    assert summary.device_planes == 1
    assert summary.window_s == pytest.approx(EXPECTED["window_s"], rel=1e-9)
    assert summary.busy_s == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    assert 0.0 < summary.idle_share < 1.0


def test_kernel_time_by_name(summary):
    assert summary.kernel_events == EXPECTED["kernel_events"]
    for k, v in EXPECTED["kernel_s"].items():
        assert summary.kernel_s[k] == pytest.approx(v, rel=1e-9)
    # kernels are leaf ops inside the busy time
    assert sum(summary.kernel_s.values()) <= summary.busy_s


def test_breakdown(summary):
    assert len(summary.device_ops) <= 10 and len(summary.idle_gaps) <= 10
    assert summary.device_ops[0][0] == EXPECTED["top_op"]
    assert not any(trace.CONTAINERS.match(name)
                   for name, _ in summary.device_ops)
    names = {name for name, _ in summary.idle_gaps}
    assert names <= set(trace.HOST_SPANS) | {"other"}
    gaps = sum(summary.idle_by_span.values())
    assert gaps == pytest.approx(summary.window_s - summary.busy_s,
                                 rel=1e-6)


def test_op_names():
    assert trace.op_name("%epoch_fused_pallas.2 = (f32[1]) custom-call()") \
        == "epoch_fused_pallas.2"
    assert trace.op_kind("%while.24 = (s32[]) while()") == "while"
    assert trace.KERNELS["epoch_finish"].match("epoch_finish_pallas.2")
    assert not trace.KERNELS["epoch_fused"].match("epoch_finish_pallas.2")


def test_roofline_share_stays_under_one_for_the_traced_kernels(summary):
    """The least time of the smallest problem an epoch can carry, once per
    traced launch, is below the launches' device time."""
    pso = dict(num_particles=64, inner_steps=12, quantized=True,
               refine_iters=6)
    peak = roofline.peak_for("TPU v5 lite")
    for k, n_events in summary.kernel_events.items():
        least = roofline.swarm_least_times([(4, 60, 1)] * n_events, pso,
                                           peak)[k]
        assert 0 < least < summary.kernel_s[k]


EXPECTED = {
    "window_s": 0.40015825600000005,
    "busy_s": 0.0037091560000000003,
    "kernel_s": {"epoch_fused": 0.000474959, "epoch_finish": 0.001217339},
    "kernel_events": {"epoch_fused": 8, "epoch_finish": 8},
    "top_op": "epoch_finish_pallas",
}
