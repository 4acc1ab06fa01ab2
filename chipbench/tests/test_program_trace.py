"""The program-span and module reduction (``program_trace.py``).

Checked on synthetic intervals, on the committed trace of a program that
writes no span (``trace_edge.xplane.pb.gz``: its tier programs were
restored from the AOT cache and run as ``jit_call_exported``), and on a
second trace recorded on a TPU v5e ("TPU v5 lite") with the program's
spans and named tier programs: a 0.4-second window of
``edge.burst_mixed`` traced by ``chipbench/run.py --trace 1`` and
committed gzipped. The numbers below were read from those files once;
the reduction must keep giving them."""
import os
import types

import pytest

from chipbench import program_trace as pt
from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "trace_edge.xplane.pb.gz")
NEW = os.path.join(DATA, "trace_edge_spans.xplane.pb.gz")
READERS = ("idle_prepare_pct", "idle_dispatch_pct", "idle_fetch_pct",
           "idle_apply_pct", "idle_drain_other_pct",
           "swarm_device_ms_per_launch", "reval_device_ms_per_launch",
           "glue_device_pct")


def test_innermost_span_takes_each_piece():
    spans = [(0, 100, "drain"), (10, 30, "dispatch"), (30, 60, "fetch"),
             (40, 50, "inner"), (120, 130, "prepare")]
    assert pt.innermost_segments(spans) == [
        (0, 10, "drain"), (10, 30, "dispatch"), (30, 40, "fetch"),
        (40, 50, "inner"), (50, 60, "fetch"), (60, 100, "drain"),
        (120, 130, "prepare")]
    # of two spans that start together the shorter is the inner one
    assert pt.innermost_segments([(0, 10, "a"), (0, 4, "b")]) == [
        (0, 4, "b"), (4, 10, "a")]


def test_idle_is_split_exactly_at_span_boundaries():
    spans = [(0, 100, "drain"), (10, 30, "dispatch"), (30, 60, "fetch"),
             (120, 130, "prepare")]
    gaps = [(5, 35), (55, 125), (140, 150)]
    split = pt.split_idle(gaps, pt.innermost_segments(spans))
    assert split == {"drain": 5 + 40, "dispatch": 20, "fetch": 5 + 5,
                     "prepare": 5, "none": 20 + 10}
    assert sum(split.values()) == sum(b - a for a, b in gaps)


@pytest.fixture(scope="module")
def old():
    return pt.reduce_program_trace(OLD)


def test_a_trace_without_program_spans(old):
    assert old.spans_seen == set()
    assert old.idle_by_program_span == {
        "none": pytest.approx(old.window_s - old.busy_s, rel=1e-9)}
    assert old.busy_s == trace.reduce_trace(OLD).busy_s


def test_modules_of_the_old_fixture(old):
    assert old.module_n["jit_call_exported"] == 7
    assert old.module_s["jit_call_exported"] == pytest.approx(0.003554554,
                                                              rel=1e-9)
    assert sum(old.module_n.values()) == 132
    assert not any(k.startswith(pt.TIER_MODULE) for k in old.module_s)


def _ctx(path):
    return types.SimpleNamespace(trace=trace.reduce_trace(path),
                                 trace_dir=path)


def test_readers_are_silent_where_the_program_writes_nothing():
    from chipbench.harness import load_metric_reader
    ctx = _ctx(OLD)
    assert all(load_metric_reader(name)(ctx) is None for name in READERS)
    ctx.trace = None
    assert all(load_metric_reader(name)(ctx) is None for name in READERS)


@pytest.fixture(scope="module")
def new():
    return pt.reduce_program_trace(NEW)


def test_idle_split_of_the_chip_trace(new):
    assert new.spans_seen == set(pt.PROGRAM_SPANS)
    whole = trace.reduce_trace(NEW)
    assert new.window_s == whole.window_s and new.busy_s == whole.busy_s
    assert set(new.idle_by_program_span) == set(EXPECTED["idle"])
    for k, v in EXPECTED["idle"].items():
        assert new.idle_by_program_span[k] == pytest.approx(v, rel=1e-9)
    assert sum(new.idle_by_program_span.values()) == pytest.approx(
        new.window_s - new.busy_s, rel=1e-9)


def test_modules_of_the_chip_trace(new):
    assert new.module_n == EXPECTED["module_n"]
    for k, v in EXPECTED["module_s"].items():
        assert new.module_s[k] == pytest.approx(v, rel=1e-9)
    # the swarm program holds the fused kernels and fits in busy time
    kernels = sum(trace.reduce_trace(NEW).kernel_s.values())
    swarm = sum(new.module_s[k] for k in pt.SWARM_MODULES
                if k in new.module_s)
    assert kernels < swarm < new.busy_s


def test_readers_on_the_chip_trace():
    from chipbench.harness import load_metric_reader
    ctx = _ctx(NEW)
    got = {name: load_metric_reader(name)(ctx) for name in READERS}
    for name, v in EXPECTED["readers"].items():
        assert got[name] == pytest.approx(v, rel=1e-9), name
    idle = sum(got[n] for n in READERS if n.startswith("idle_"))
    none = 100 * EXPECTED["idle"]["none"] / ctx.trace.window_s
    assert idle + none == pytest.approx(100 * ctx.trace.idle_share,
                                        abs=1e-9)


EXPECTED = {
    "idle": {"immsched.drain": 0.009745327, "immsched.prepare": 0.004651799,
             "immsched.dispatch": 0.043733257, "immsched.fetch": 0.014668747,
             "immsched.apply": 0.102592219, "none": 0.217266585},
    "module_n": {"jit__take": 33, "jit_immsched_revalidate": 11,
                 "jit_dynamic_slice": 178, "jit_write": 24,
                 "jit_convert_element_type": 14, "jit__reduce_sum": 5,
                 "jit_maximum": 5, "jit_true_divide": 5,
                 "jit_broadcast_in_dim": 14, "jit_immsched_swarm_batch": 4,
                 "jit_concatenate": 4},
    "module_s": {"jit_immsched_swarm_batch": 0.006171718,
                 "jit_immsched_revalidate": 0.001297057,
                 "jit_write": 0.000279883},
    "readers": {"idle_prepare_pct": 1.1615501343578547,
                "idle_dispatch_pct": 10.920155953483071,
                "idle_fetch_pct": 3.6627732730307954,
                "idle_apply_pct": 25.617187192206817,
                "idle_drain_other_pct": 2.4333996129693545,
                "swarm_device_ms_per_launch": 1.5429295,
                "reval_device_ms_per_launch": 0.11791427272727274,
                "glue_device_pct": 6.1943840531928664},
}
