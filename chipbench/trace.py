"""Reduction of one profiler trace of the measured window to numbers.

The benchmark writes host spans into the profiler's own trace with
``jax.profiler.TraceAnnotation`` from its own code: ``chipbench.window``
around the whole measured window, and inside it ``gen.wait`` (the load
generator sleeping until the next request is due), ``fe.submit``
(handing due requests to the front end, which may drain a full batch)
and ``fe.drain`` (the front end draining what is queued). The device's
operations come from the TPU planes of the same trace.

``reduce_trace`` returns, over the window span only:

* ``busy_s`` — the union of the device operations' intervals, averaged
  over the device planes, and ``window_s`` — the span's length;
* ``kernel_s`` — device seconds of each named kernel (``KERNELS``);
* ``device_ops`` — the device operations that took most time, by
  instruction name without its numeric suffix, control flow left out;
* ``idle_gaps`` — the longest gaps in which no operation ran on the
  device, each named by the benchmark span the host was in.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"
HOST_SPANS = ("gen.wait", "fe.submit", "fe.drain")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_OP_LINE = "XLA Ops"

#: HLO instruction names of the kernels whose roofline share is
#: reported: the ``name`` each ``pallas_call`` gives its custom call.
KERNELS = {
    "epoch_fused": re.compile(r"^epoch_fused_pallas(\.\d+)?$"),
    "epoch_finish": re.compile(r"^epoch_finish_pallas(\.\d+)?$"),
}
#: Control-flow ops whose device time is that of the ops inside them:
#: they count toward busy time but are left out of the top operations.
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device-op event, whose name is the
    instruction's text (``%epoch_fused_pallas.2 = (...) custom-call(...)``)
    or the bare name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(event_name: str) -> str:
    """The instruction name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", op_name(event_name))


def trace_options():
    """Profiler options of the traced run: host spans and device ops, no
    Python call tracing (which slows every host call it records) and no
    HLO protos (which only grow the file)."""
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    kernel_s: Dict[str, float]
    kernel_events: Dict[str, int]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    idle_by_span: Dict[str, float]
    device_planes: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb*"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def load(path: str):
    """The profile at ``path``: an ``.xplane.pb`` file, or one gzipped."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce_trace(path: str, top: int = 10) -> Optional[TraceSummary]:
    """Numbers of the window span of the trace at ``path`` (a file, or a
    directory holding one); None when the trace holds no window span or
    no device operation inside it."""
    if os.path.isdir(path):
        path = find_xplane(path)
    data = load(path)
    window = None
    host_spans: List[Tuple[float, float, str]] = []
    device_lines = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    device_lines.append(line)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in HOST_SPANS:
                    host_spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None or not device_lines:
        return None
    lo, hi = window
    busy_total = 0.0
    kernel_ns: Dict[str, float] = collections.defaultdict(float)
    kernel_n: Dict[str, int] = collections.defaultdict(int)
    op_ns: Dict[str, float] = collections.defaultdict(float)
    gaps_all: List[Tuple[float, float]] = []
    for line in device_lines:
        ivs = []
        for ev in line.events:
            iv = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if iv is None:
                continue
            ivs.append(iv)
            dur = iv[1] - iv[0]
            name = op_name(ev.name)
            if not CONTAINERS.match(name):
                op_ns[op_kind(ev.name)] += dur
            for kernel, pat in KERNELS.items():
                if pat.match(name):
                    kernel_ns[kernel] += dur
                    kernel_n[kernel] += 1
        busy = _union(ivs)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps_all += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    planes = len(device_lines)
    if busy_total <= 0:
        return None
    host_spans.sort()
    idle_by_span: Dict[str, float] = collections.defaultdict(float)
    named_gaps = []
    for a, b in gaps_all:
        name = _host_span_over(host_spans, a, b)
        idle_by_span[name] += (b - a) / planes
        named_gaps.append((name, (b - a) * 1e-9))
    named_gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / planes * 1e-9,
        kernel_s={k: v / planes * 1e-9 for k, v in kernel_ns.items()},
        kernel_events=dict(kernel_n),
        device_ops=[(k, v / planes * 1e-9) for k, v in ops],
        idle_gaps=named_gaps[:top],
        idle_by_span={k: v * 1e-9 for k, v in idle_by_span.items()},
        device_planes=planes)


def _host_span_over(spans: List[Tuple[float, float, str]], a: float,
                    b: float) -> str:
    """Name of the benchmark span that overlaps [a, b] the most. The
    spans are sorted and do not overlap one another."""
    best, best_ov = "other", 0.0
    k = max(bisect.bisect_right(spans, (a, float("inf"), "")) - 1, 0)
    for s, e, name in spans[k:]:
        if s >= b:
            break
        ov = min(e, b) - max(s, a)
        if ov > best_ov:
            best, best_ov = name, ov
    return best
