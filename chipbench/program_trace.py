"""Program spans and XLA modules of one traced window.

The served path writes its own spans into the profiler trace
(``src/repro/core/service.py``): ``immsched.drain`` around a front-end
drain round and, inside it, ``immsched.prepare``, ``immsched.dispatch``,
``immsched.fetch`` and ``immsched.apply``. Its tier programs run as the
XLA modules ``jit_immsched_swarm``, ``jit_immsched_swarm_batch`` and
``jit_immsched_revalidate``. ``reduce_program_trace`` reads both from the
trace that ``trace.py`` reduces, over the same window span, with the
same idle intervals:

* ``idle_by_program_span`` — the device's idle time split exactly at
  program-span boundaries, each piece credited to the innermost program
  span open on the host over it, else to ``"none"``; averaged over the
  device planes, the values sum to ``window_s - busy_s``;
* ``module_s``, ``module_n`` — device seconds and event counts of each
  XLA module on the device planes' ``XLA Modules`` line, clipped to the
  window, keyed by name without the ``(hash)`` suffix and averaged over
  the device planes.

A program that writes none of these spans leaves ``spans_seen`` empty,
and the readers of the idle shares report nothing for it.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from chipbench import trace

PROGRAM_SPANS = ("immsched.drain", "immsched.prepare", "immsched.dispatch",
                 "immsched.fetch", "immsched.apply")
NO_SPAN = "none"
MODULE_LINE = "XLA Modules"
#: Prefix of the served tier programs' XLA modules.
TIER_MODULE = "jit_immsched_"
SWARM_MODULES = ("jit_immsched_swarm_batch", "jit_immsched_swarm")
REVAL_MODULES = ("jit_immsched_revalidate",)

Span = Tuple[float, float, str]


@dataclasses.dataclass
class ProgramSummary:
    window_s: float
    busy_s: float
    idle_by_program_span: Dict[str, float]
    module_s: Dict[str, float]
    module_n: Dict[str, int]
    spans_seen: Set[str]


def module_name(event_name: str) -> str:
    """An ``XLA Modules`` event's name without its ``(hash)`` suffix."""
    return re.sub(r"\(\d+\)$", "", event_name)


def innermost_segments(spans: Sequence[Span]) -> List[Span]:
    """Disjoint segments covering the spans' union, each named by the
    innermost span open over it: the one that started last (of two that
    started together, the one that ends first)."""
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    by_start = sorted(spans)
    out: List[Span] = []
    active: List[Span] = []
    k = 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(by_start) and by_start[k][0] <= a:
            active.append(by_start[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            out.append((a, b, max(active, key=lambda sp: (sp[0], -sp[1]))[2]))
    return out


def split_idle(gaps: Sequence[Tuple[float, float]],
               segments: Sequence[Span]) -> Dict[str, float]:
    """Length of the sorted, disjoint ``gaps`` under each segment name,
    the rest under ``NO_SPAN``."""
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        covered = 0.0
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            ov = min(e, b) - max(s, a)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        out[NO_SPAN] += (b - a) - covered
    return out


def reduce_program_trace(path: str) -> Optional[ProgramSummary]:
    """Program spans and modules of the window span of the trace at
    ``path`` (a file, or a directory holding one); None where
    ``trace.reduce_trace`` gives None."""
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    data = trace.load(path)
    window = None
    spans: List[Span] = []
    planes = []                      # (ops line, modules line) per plane
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if trace.DEVICE_OP_LINE in lines:
                planes.append((lines[trace.DEVICE_OP_LINE],
                               lines.get(MODULE_LINE)))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in PROGRAM_SPANS:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None or not planes:
        return None
    lo, hi = window
    segments = innermost_segments(spans)
    busy_total = 0.0
    idle: Dict[str, float] = collections.defaultdict(float)
    mod_ns: Dict[str, float] = collections.defaultdict(float)
    mod_n: Dict[str, int] = collections.defaultdict(int)
    for ops, modules in planes:
        ivs = [iv for iv in (trace._clip(ev.start_ns, ev.end_ns, lo, hi)
                             for ev in ops.events) if iv is not None]
        busy = trace._union(ivs)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, ns in split_idle(gaps, segments).items():
            idle[name] += ns
        for ev in (modules.events if modules is not None else ()):
            iv = trace._clip(ev.start_ns, ev.end_ns, lo, hi)
            if iv is not None:
                mod_ns[module_name(ev.name)] += iv[1] - iv[0]
                mod_n[module_name(ev.name)] += 1
    n = len(planes)
    if busy_total <= 0:
        return None
    return ProgramSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n * 1e-9,
        idle_by_program_span={k: v / n * 1e-9 for k, v in idle.items()},
        module_s={k: v / n * 1e-9 for k, v in mod_ns.items()},
        module_n={k: v // n for k, v in mod_n.items()},
        spans_seen={name for _, _, name in spans})


def summary_for(ctx) -> Optional[ProgramSummary]:
    """The program summary of a traced run's window, reduced once per
    run context from the trace at ``ctx.trace_dir`` (a file, or a
    directory holding one), by default the harness's trace directory;
    None when the run has no reduced trace."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "program"):
        path = getattr(ctx, "trace_dir", None)
        if path is None:
            from chipbench import harness
            path = harness.TRACE_DIR
        try:
            ctx.program = reduce_program_trace(path)
        except FileNotFoundError:
            ctx.program = None
    return ctx.program


def idle_pct(ctx, span: str) -> Optional[float]:
    """Share of the window (%) in which the device idled with ``span``
    the innermost program span open; None when the trace holds no
    program span."""
    s = summary_for(ctx)
    if s is None or not s.spans_seen:
        return None
    return 100.0 * s.idle_by_program_span.get(span, 0.0) / s.window_s


def ms_per_launch(ctx, modules: Sequence[str]) -> Optional[float]:
    """Device milliseconds per event of the named XLA modules; None when
    none of them ran in the window."""
    s = summary_for(ctx)
    if s is None:
        return None
    n = sum(s.module_n.get(m, 0) for m in modules)
    if n == 0:
        return None
    return 1e3 * sum(s.module_s.get(m, 0.0) for m in modules) / n
