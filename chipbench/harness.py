"""The benchmark harness: one cell, one run, on the served matcher path.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``: the platform, every ``PSOConfig`` field, the
``MatcherService`` and front-end options) and a traffic mix
(``traffic/<mix>.json``, read by ``generator.py``); ``cells/<cell>.json``
holds what belongs to the pair: the offered rate, fixed from a sweep on
the chip, the shape buckets its traffic may reach, and the limits of
its checks. Per-layer metrics are read by ``metrics/<metric>.py``. The
query windows come from the window set the configuration names under
``windows`` (its platform's name when it names none), frozen in
``data/windows/<set>.json`` by ``freeze_windows.py``. All of these are
found by name, so a later cell, configuration, mix, window set or metric
is a new file and a new entry.

One run:

1. **Set-up** (``setup_s``, from process start): the requests are drawn
   from ``--seed``; the service is built with JAX's compile cache kept
   under ``.chipbench_cache/`` in the checkout; a store fill grows the
   service's per-bucket carry storage to what a long run needs; every
   (window size, free-engine count) class of the traffic is served at
   every batch class the service launches, cold (Tier 2) and again as
   exact repeats (Tier 0/1); a mix with a pool of repeated states has
   each state served once; then pre-roll rounds of the cell's own
   traffic run until one builds no program, leaving the stores in the
   steady state the window serves from. The window should build none.
2. **Window** (``--seconds``): an open loop on the wall clock. Each
   request is handed to ``AsyncServiceFrontEnd.submit`` once it is due
   and the loop is free; when no full batch has drained it, the front
   end is flushed at once. A request is timed from its due time to the
   moment its answer can be taken.
3. **After the window**: requests due in the window but not yet answered
   are served (late, with their wait counted), device memory is read,
   the service is dropped, and every answer is compared with the plain
   reference (``reference.py``): each served mapping must be valid on
   its own window and free mask, no request may be answered "found"
   where the reference proves no mapping exists, and of the requests
   the reference can map no more than the cell's limit may go without a
   valid mapping.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types
from typing import Dict, List, Optional

import numpy as np

from chipbench import generator, reference, roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_ROOT = os.path.join(ROOT, ".chipbench_cache")
JAX_CACHE_DIR = os.path.join(CACHE_ROOT, "jax")
PERSIST_DIR = os.path.join(CACHE_ROOT, "persist")
TRACE_DIR = os.path.join(CACHE_ROOT, "trace")
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")
WINDOWS_DIR = os.path.join(HERE, "data", "windows")

#: Streams of one seed: the window, the warm-up pool, the pre-roll, keys,
#: the store fill.
STREAM_WINDOW, STREAM_WARM, STREAM_PREROLL, STREAM_KEYS, STREAM_FILL = \
    0, 1, 2, 3, 4
#: Requests still unanswered this long after the window closes are lost.
LATE_LIMIT_S = 60.0
#: A traced run measures a window of at most this many seconds, so that
#: its trace stays some tens of MB and is read within the run's time.
TRACE_WINDOW_S = 5.0
FAULTS = ("alter_answer", "claim_found", "drop_half")


class Refused(Exception):
    """The run cannot measure this cell here; no result is printed."""


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict
    config: Dict
    traffic: Dict
    params: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    windows: Dict[str, reference.Window]

    @property
    def platform(self) -> Dict:
        return self.config["platform"]


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_file: str = SPEC_FILE,
              windows_dir: str = WINDOWS_DIR) -> Cell:
    """The cell, its files and its window set; raises :class:`Refused`
    when the set was lowered for another platform than the
    configuration's."""
    spec = _read_json(spec_file)
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in {spec_file}")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = _read_json(os.path.join(ROOT, conf["file"]))
    return Cell(
        name=name, entry=entry, config=config,
        traffic=_read_json(os.path.join(HERE, "traffic",
                                        entry["traffic"] + ".json")),
        params=_read_json(os.path.join(HERE, "cells", name + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in spec["per_layer"] if _in_cell(m, name)],
        windows=load_windows(window_set(config), config["platform"]["name"],
                             windows_dir))


def window_set(config: Dict) -> str:
    """The window set a configuration is served from: its ``windows``
    key, else its platform's name."""
    return config.get("windows", config["platform"]["name"])


def load_windows(set_name: str, platform_name: str,
                 windows_dir: str = WINDOWS_DIR
                 ) -> Dict[str, reference.Window]:
    """The frozen windows of ``<windows_dir>/<set_name>.json``; raises
    :class:`Refused` when the set was lowered for another platform than
    ``platform_name``."""
    data = _read_json(os.path.join(windows_dir, set_name + ".json"))
    if data["platform"] != platform_name:
        raise Refused(f"window set {set_name!r} was lowered for platform "
                      f"{data['platform']!r}, not {platform_name!r}")
    return {name: reference.Window(name, w["n"], w["edges"], w["types"],
                                   w["macs"])
            for name, w in data["windows"].items()}


def bucket_of(n: int, m: int, n_multiple: int, m_multiple: int):
    """The shape class the cell's entry lists for an (n, m) problem: n
    rounded up to ``n_multiple``, m plus the ``n_pad - n`` dummy engines
    rounded up to ``m_multiple`` (the service's bucketing as of this
    benchmark, frozen here so the listed buckets describe the traffic)."""
    n_pad = -(-max(n, 1) // n_multiple) * n_multiple
    m_pad = -(-(max(m, 1) + n_pad - n) // m_multiple) * m_multiple
    return n_pad, m_pad


def assert_buckets(cell: Cell, windows, reqs) -> None:
    listed = {tuple(b) for b in cell.params["buckets"]}
    svc = cell.config["service"]
    seen = {bucket_of(windows[r.window].n, int(r.free.sum()),
                      svc["n_multiple"], svc["m_multiple"]) for r in reqs}
    if not seen <= listed:
        raise Refused(f"traffic reaches buckets {sorted(seen - listed)} "
                      f"that {cell.name} does not list {sorted(listed)}")


def load_metric_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def check_device(cell: Cell) -> Dict:
    """The chip this run measures on; raises :class:`Refused` when it is
    not a TPU, when it has fewer chips than the cell asks for, when the
    kernel backend does not resolve to ``pallas``, or when its kind has
    no peak entry."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX finds no device: {e}")
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX reports platform "
                      f"{devices[0].platform!r}")
    if len(devices) < cell.entry["chips"]:
        raise Refused(f"{cell.name} needs {cell.entry['chips']} chips, "
                      f"JAX finds {len(devices)}")
    from repro.kernels.backend import resolve_backend_name
    backend = resolve_backend_name(cell.config["pso"]["backend"])
    if backend != "pallas":
        raise Refused(f"kernel backend resolves to {backend!r}, not "
                      f"'pallas'")
    kind = devices[0].device_kind
    try:
        peak = roofline.peak_for(kind)
    except KeyError as e:
        raise Refused(str(e))
    return {"devices": devices, "kind": kind, "peak": peak}


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # backend without memory stats
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Problem:
    req: generator.Request
    query: object
    target: object
    sig: bytes
    key: np.ndarray


def build_service(cell: Cell, persist_dir):
    from repro.core.pso import PSOConfig
    from repro.core.service import AsyncServiceFrontEnd, MatcherService
    # JSON has no infinities: float fields may be written as "-inf"
    cfg = PSOConfig(**{k: float(v) if k == "early_exit_fitness" else v
                       for k, v in cell.config["pso"].items()})
    svc = MatcherService(cfg, persist_dir=persist_dir,
                         **cell.config["service"])
    fe = AsyncServiceFrontEnd(svc, **cell.config["frontend"])
    return svc, fe


def make_problems(cell: Cell, windows, reqs, seed: int, stream: int
                  ) -> List[Problem]:
    from repro.accel.platform import get_platform
    from repro.accel.target_graph import (free_engine_graph,
                                          free_engine_signature)
    from repro.core.graphs import Graph
    plat = get_platform(cell.platform["name"])
    for key in ("engines", "noc_rows", "noc_cols"):
        if getattr(plat, key) != cell.platform[key]:
            raise Refused(f"platform {plat.name}: {key} "
                          f"{getattr(plat, key)} != {cell.platform[key]}")
    queries = {name: Graph(adj=w.adj.copy(), types=w.types.copy(),
                           weights=w.macs.copy())
               for name, w in windows.items()}
    keys = generator.rng_for(seed, STREAM_KEYS * 1000 + stream).integers(
        0, 1 << 32, size=(len(reqs), 2), dtype=np.uint32)
    # one target graph per free mask: requests that repeat a state share it
    targets: Dict[bytes, object] = {}
    out = []
    for i, r in enumerate(reqs):
        sig = free_engine_signature(r.free)
        if sig not in targets:
            targets[sig] = free_engine_graph(plat, r.free)
        out.append(Problem(req=r, query=queries[r.window],
                           target=targets[sig], sig=sig, key=keys[i]))
    return out


def plant_fault(kind: str):
    """Break the timed path where answers are produced (for the tests and
    the control runs that show ``correct`` can fail); returns the undo.
    ``alter_answer`` puts tile 0 on tile 1's engine; ``claim_found``
    serves a made-up mapping where none was found; ``drop_half`` answers
    "not found" for every second request drained, without its search."""
    from repro.core import service as S
    orig = S.MatcherService.drain
    served = [0]

    def drain(self):
        results = orig(self)
        for r in results:
            served[0] += 1
            if kind == "drop_half" and served[0] % 2:
                r.mapping = None             # found is mapping is not None
            elif kind == "alter_answer" and r.found \
                    and r.mapping.shape[0] > 1:
                M = np.array(r.mapping)
                M[0] = M[1]          # tile 0 onto tile 1's engine
                r.mapping = M
            elif kind == "claim_found" and not r.found:
                M = np.zeros((r.all_mappings.shape[-2],
                              r.all_mappings.shape[-1]), np.uint8)
                M[np.arange(M.shape[0]), np.arange(M.shape[0])] = 1
                r.mapping = M
        return results

    S.MatcherService.drain = drain

    def undo():
        S.MatcherService.drain = orig

    return undo


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def _serve_closed(fe, problems: List[Problem]) -> None:
    """Submit ``problems`` now and drain them, dropping the results."""
    rids = [fe.submit(p.query, p.target, key=p.key,
                      workload_key=(p.req.window, p.sig), engine_sig=p.sig)
            for p in problems]
    fe.flush()
    for rid in rids:
        fe.take_result(rid)


def warm_up(svc, fe, problems: List[Problem], windows,
            batch_classes) -> int:
    """Serve every (window, free count) class at every batch class, cold
    and then as exact repeats; returns the drains made."""
    classes: Dict[tuple, List[Problem]] = {}
    for p in problems:
        classes.setdefault((p.req.window, int(p.req.free.sum())),
                           []).append(p)
    drains = 0
    for cls, members in sorted(classes.items()):
        for b in batch_classes:
            if len(members) < b:
                raise Refused(f"warm-up pool has {len(members)} problems "
                              f"of class {cls}, needs {b}")
            svc.clear_carries()
            for _ in range(2):          # cold (Tier 2), then repeats
                _serve_closed(fe, members[:b])
                drains += 1
    svc.clear_carries()
    return drains


def fill_stores(cell: Cell, fe, windows, templates, seed: int) -> int:
    """Grow the service's per-bucket state to what a long run reaches.

    The service keeps stored carries in device storage that grows, and
    builds new programs as it does, while its warm-start stores fill up.
    So for each listed bucket in turn, the traffic's ``fill`` distinct
    problems of that bucket are served in a row: more than the bucket
    ever holds at once in the window. Returns the drains."""
    svc_opts = cell.config["service"]
    per_bucket = cell.traffic["fill"]
    batch = max(svc_opts["batch_classes"])
    rng = generator.rng_for(seed, STREAM_FILL)
    drains = 0
    for k, bucket in enumerate(sorted(templates)):
        reqs = generator.requests_like(rng, cell.traffic["masks"],
                                       cell.platform, templates[bucket],
                                       per_bucket)
        problems = make_problems(cell, windows, reqs, seed,
                                 STREAM_FILL * 1000 + k)
        for pos in range(0, len(problems), batch):
            _serve_closed(fe, problems[pos:pos + batch])
            drains += 1
    return drains


def serve_pool(cell: Cell, fe, windows, seed: int) -> int:
    """Serve every state of the mix's pool once, in batches of the
    largest batch class: the store filling that traffic of repeated
    states needs. Returns the states served (0 for a mix without one)."""
    reqs = generator.pool_states(cell.traffic, cell.platform, seed)
    if not reqs:
        return 0
    assert_buckets(cell, windows, reqs)
    problems = make_problems(cell, windows, reqs, seed,
                             generator.STREAM_POOL)
    batch = max(cell.config["service"]["batch_classes"])
    for pos in range(0, len(problems), batch):
        _serve_closed(fe, problems[pos:pos + batch])
    return len(problems)


def pre_roll(cell: Cell, fe, windows, seed: int, compiled: List[str]
             ) -> int:
    """Serve rounds of the cell's own traffic, closed loop, in batches of
    1 to the largest batch class drawn from the seed, until a round
    builds no program (at most ``preroll_rounds``). The warm-start stores
    end full, as in the steady state the window serves from. Returns the
    rounds served."""
    count = cell.traffic["preroll"]
    top = max(cell.config["service"]["batch_classes"])
    rng = generator.rng_for(seed, STREAM_PREROLL)
    for r in range(cell.traffic["preroll_rounds"]):
        reqs = generator.draw_requests(cell.traffic, cell.platform, 1.0,
                                       count, seed, STREAM_PREROLL * 1000 + r)
        assert_buckets(cell, windows, reqs)
        problems = make_problems(cell, windows, reqs, seed,
                                 STREAM_PREROLL * 1000 + r)
        before = len(compiled)
        pos = 0
        while pos < len(problems):
            b = int(rng.integers(1, top + 1))
            _serve_closed(fe, problems[pos:pos + b])
            pos += b
        if len(compiled) == before:
            return r + 1
    return cell.traffic["preroll_rounds"]


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Answer:
    """What the checks read of a served result. The result itself is
    dropped at once, as a caller would drop it: a Tier-0/1 result pins
    the service's stored carry until it is freed."""
    found: bool
    mapping: Optional[np.ndarray]
    tier: int
    epochs_run: int

    @classmethod
    def of(cls, res) -> "Answer":
        return cls(found=bool(res.found),
                   mapping=None if res.mapping is None
                   else np.asarray(res.mapping),
                   tier=int(res.tier), epochs_run=int(res.epochs_run))


@dataclasses.dataclass
class Record:
    problem: Problem
    due: float                       # absolute host-clock due time
    submitted: Optional[float] = None
    done: Optional[float] = None
    result: object = None
    shed: bool = False

    @property
    def latency(self) -> float:
        return math.inf if self.done is None else self.done - self.due


def _annotate(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def run_window(fe, problems: List[Problem], seconds: float,
               tracing: bool = False, clock=time.perf_counter):
    """The open loop. Returns ``(records, t0, end)``."""
    span = _annotate(tracing)
    t0 = clock()
    end = t0 + seconds
    recs = [Record(problem=p, due=t0 + p.req.due) for p in problems]
    queued: Dict[int, Record] = {}

    def collect():
        now = clock()
        for rid in list(queued):
            try:
                res = fe.take_result(rid)
            except KeyError:
                continue
            rec = queued.pop(rid)
            rec.done = now
            if res is None:
                rec.shed, rec.done = True, None
            else:
                rec.result = Answer.of(res)

    def submit(rec: Record):
        p = rec.problem
        rec.submitted = clock()
        rid = fe.submit(p.query, p.target, now=rec.due, key=p.key,
                        workload_key=(p.req.window, p.sig),
                        engine_sig=p.sig)
        queued[rid] = rec
        collect()

    i, n = 0, len(recs)
    with span("chipbench.window"):
        while True:
            now = clock()
            if now >= end:
                break
            if i < n and recs[i].due <= now:
                with span("fe.submit"):
                    while i < n and recs[i].due <= now:
                        submit(recs[i])
                        i += 1
                if fe.depth:
                    with span("fe.drain"):
                        fe.flush()
                        collect()
                continue
            wake = min(recs[i].due if i < n else end, end)
            with span("gen.wait"):
                time.sleep(max(wake - clock(), 0.0))
    # requests due in the window and not yet answered: served late
    while i < n and recs[i].due < end:
        submit(recs[i])
        i += 1
    late_until = clock() + LATE_LIMIT_S
    while queued and clock() < late_until:
        fe.flush()
        collect()
    return [r for r in recs if r.due < end], t0, end


# ---------------------------------------------------------------------------
# Numbers
# ---------------------------------------------------------------------------

def nearest_rank(values: List[float], q: float) -> float:
    """The smallest value with at least a share ``q`` of the values at or
    below it."""
    vals = sorted(values)
    k = max(int(math.ceil(q * len(vals))) - 1, 0)
    return vals[k]


def check_answers(recs: List[Record], windows, platform: Dict,
                  limits: Dict):
    """Compare every answer with the plain reference. Returns the checks
    (number and limit each) and per-request verdicts. A request the
    reference can map and that was not served a valid mapping (answered
    "not found", shed, or served an invalid one) is a missed mapping."""
    mesh = reference.mesh_adjacency(platform["noc_rows"],
                                    platform["noc_cols"])
    exists_cache: Dict[tuple, Optional[bool]] = {}
    invalid = false_found = unanswered = undecided = 0
    mappable = mapped = 0
    for rec in recs:
        req = rec.problem.req
        w = windows[req.window]
        key = (req.window, rec.problem.sig)
        if key not in exists_cache:
            exists_cache[key] = reference.mapping_exists(w, req.free, mesh)
        exists = exists_cache[key]
        valid = False
        if rec.shed:
            pass
        elif rec.result is None:
            unanswered += 1
        elif rec.result.found:
            try:
                eng = reference.engines_from_matrix(rec.result.mapping,
                                                    req.free)
                reference.check_mapping(eng, w, req.free, mesh)
                valid = True
            except reference.InvalidMapping:
                invalid += 1
            if exists is False:
                false_found += 1
        if exists is None and valid:
            exists = True
        if exists is None:
            undecided += 1
        elif exists:
            mappable += 1
            mapped += valid
    missed = 100.0 * (mappable - mapped) / mappable if mappable else 0.0
    checks = {"invalid_mappings": {"value": invalid, "limit": 0},
              "found_without_mapping": {"value": false_found, "limit": 0},
              "never_answered": {"value": unanswered, "limit": 0},
              "missed_mappings_pct": {
                  "value": missed, "limit": limits["missed_mappings_pct"]}}
    return checks, {"mappable": mappable, "mapped": mapped,
                    "undecided": undecided, "invalid": invalid}


def counters(svc) -> Dict[str, float]:
    return {k: v for k, v in svc.stats_dict().items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def end_to_end(recs: List[Record], seconds: float, end: float,
               verdict: Dict) -> Dict[str, float]:
    lat = [r.latency for r in recs]
    answered = sum(r.done is not None and r.done <= end for r in recs)
    p50, p95 = nearest_rank(lat, 0.5), nearest_rank(lat, 0.95)
    out = {"decisions_per_s": answered / seconds,
           "mapped_pct": 100.0 * verdict["mapped"] / verdict["mappable"]
           if verdict["mappable"] else None}
    # a shed request never comes: its latency is beyond every limit, and
    # a percentile that lands on one is reported as the whole window
    for name, v in (("decision_p50_ms", p50), ("decision_p95_ms", p95)):
        out[name] = 1e3 * (v if math.isfinite(v) else seconds + LATE_LIMIT_S)
    return out


def per_layer(cell: Cell, ctx) -> Dict[str, float]:
    out = {}
    for m in cell.per_layer:
        val = load_metric_reader(m["name"])(ctx)
        if val is not None:
            out[m["name"]] = val
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated event rates: one window each, "
                         "one JSON line each, no result line")
    ap.add_argument("--fault", choices=FAULTS, default=None,
                    help="break the served answers (control runs)")
    return ap.parse_args(argv)


def setup_caches() -> None:
    os.makedirs(JAX_CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = JAX_CACHE_DIR


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def prepare(cell: Cell, seed: int, seconds: float, rate: float,
            persist_dir, windows, compiled: List[str]):
    """Set-up: draw the requests, build the service, grow its stores,
    warm every program up, and pre-roll to the steady state."""
    svc_opts = cell.config["service"]
    window_reqs = generator.draw_requests(
        cell.traffic, cell.platform, rate, seconds, seed, STREAM_WINDOW)
    warm_reqs = generator.draw_requests(
        cell.traffic, cell.platform, 1.0, cell.traffic["warm_pool"], seed,
        STREAM_WARM)
    for reqs in (window_reqs, warm_reqs):
        assert_buckets(cell, windows, reqs)
    problems = make_problems(cell, windows, window_reqs, seed, STREAM_WINDOW)
    warm = make_problems(cell, windows, warm_reqs, seed, STREAM_WARM)
    # one template request per bucket for the store fill, preferring a
    # window the mesh can hold: the swarm stops at its first mapping
    mesh = reference.mesh_adjacency(cell.platform["noc_rows"],
                                    cell.platform["noc_cols"])
    templates = {}
    for r in sorted(warm_reqs, key=lambda r: not reference.mapping_exists(
            windows[r.window], r.free, mesh)):
        templates.setdefault(bucket_of(windows[r.window].n, int(r.free.sum()),
                                       svc_opts["n_multiple"],
                                       svc_opts["m_multiple"]), r)
    svc, fe = build_service(cell, persist_dir)
    t = time.perf_counter()
    drains = fill_stores(cell, fe, windows, templates, seed)
    log(f"store fill: {drains} drains in {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    drains = warm_up(svc, fe, warm, windows, svc.batch_classes)
    log(f"warm-up: {drains} drains in {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    states = serve_pool(cell, fe, windows, seed)
    if states:
        log(f"pool: {states} states in {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    rounds = pre_roll(cell, fe, windows, seed, compiled)
    log(f"pre-roll: {rounds} rounds in {time.perf_counter() - t:.3f}s, "
        f"{len(compiled)} programs built in set-up")
    return svc, fe, problems


def measure(cell: Cell, svc, fe, problems, seconds: float, tracing: bool,
            windows, compiled: List[str]):
    """One window with the service's counters read around it. Programs
    built inside the window (the service's or any other) are counted as
    ``xla_compiles`` and named in the log."""
    before = counters(svc)
    first = len(compiled)
    recs, t0, end = run_window(fe, problems, seconds, tracing=tracing)
    after = counters(svc)
    delta = {k: after[k] - before.get(k, 0) for k in after}
    delta["xla_compiles"] = len(compiled) - first
    if compiled[first:]:
        log(f"built in the window: {compiled[first:]}")
    return recs, t0, end, delta


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (Refused, OSError, KeyError, StopIteration) as e:
        print(f"chipbench: cannot load cell {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    setup_caches()
    try:
        import jax  # noqa: F401
        dev = check_device(cell)
        result = run_cell(cell, args, dev, t_start)
    except (Refused, ImportError) as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 3
    if result is not None:
        print(json.dumps(result))
    return 0


def run_cell(cell: Cell, args, dev: Dict, t_start: float,
             persist_dir=PERSIST_DIR) -> Optional[Dict]:
    """Set-up, window and checks of one run; returns the result line
    (None for a sweep, whose lines are printed as they come)."""
    import jax
    compiled: List[str] = []

    def on_compile(event, duration, **kw):
        # one per program built, compiled or loaded from the disk cache
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(str(kw.get("fun_name", "?")))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    undo = plant_fault(args.fault) if args.fault else None
    try:
        return _run_cell(cell, args, dev, t_start, persist_dir, compiled)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        if undo is not None:
            undo()


def _run_cell(cell: Cell, args, dev: Dict, t_start: float, persist_dir,
              compiled: List[str]) -> Optional[Dict]:
    import jax
    windows = cell.windows
    rate = cell.params["rate_hz"]
    svc, fe, problems = prepare(cell, args.seed, args.seconds, rate,
                                persist_dir, windows, compiled)
    if args.sweep:
        sweep(cell, args, svc, fe, windows, compiled)
        return None
    if args.trace:
        args.seconds = min(args.seconds, TRACE_WINDOW_S)
        problems = [p for p in problems if p.req.due < args.seconds]
        from chipbench import trace as trace_mod
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(
            TRACE_DIR, profiler_options=trace_mod.trace_options())
    setup_s = time.perf_counter() - t_start
    recs, t0, end, delta = measure(cell, svc, fe, problems, args.seconds,
                                   bool(args.trace), windows, compiled)
    if args.trace:
        jax.profiler.stop_trace()
    mem = memory_peak_bytes(dev["devices"])
    del svc, fe, problems
    gc.collect()
    return score(cell, args, dev, recs, end, delta, setup_s, mem, windows,
                 bool(args.trace))


def score(cell: Cell, args, dev: Dict, recs: List[Record], end: float,
          delta: Dict, setup_s: float, mem, windows, tracing: bool) -> Dict:
    """The result line of one window."""
    checks, verdict = check_answers(recs, windows, cell.platform,
                                    cell.params["limits"])
    e2e = end_to_end(recs, args.seconds, end, verdict)
    e2e["setup_s"] = setup_s
    shed = sum(r.shed for r in recs)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"window: {len(recs)} requests, correct {correct}, shed {shed}, "
        f"mappable "
        f"{verdict['mappable']}, mapped {verdict['mapped']}, undecided "
        f"{verdict['undecided']}, counters "
        f"{json.dumps({k: delta[k] for k in sorted(delta) if delta[k]})}")
    device = {"platform": dev["devices"][0].platform, "kind": dev["kind"],
              "count": len(dev["devices"]), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(recs),
              "failed": shed + checks["never_answered"]["value"]
              + checks["invalid_mappings"]["value"]}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if tracing:
        summary = None
        from chipbench import trace as trace_mod
        try:
            summary = trace_mod.reduce_trace(TRACE_DIR)
        except FileNotFoundError as e:
            log(f"trace: {e}")
        ctx = types.SimpleNamespace(
            delta=delta, records=recs, end=end, seconds=args.seconds,
            trace=summary, pso=cell.config["pso"], peak=dev["peak"],
            windows=windows)
        values = per_layer(cell, ctx)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": [[k, v] for k, v in summary.device_ops],
                "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
            log(f"trace: kernels {summary.kernel_s} events "
                f"{summary.kernel_events}, idle by span "
                f"{summary.idle_by_span}")
    else:
        values = {k: v for k, v in e2e.items() if v is not None}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items() if k in units}
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result


def sweep(cell: Cell, args, svc, fe, windows, compiled: List[str]) -> None:
    """One window per offered rate, back to back in this process: the
    knee is the highest rate at which the completed rate keeps up,
    nothing is shed and the backlog does not grow."""
    for k, rate in enumerate(float(r) for r in args.sweep.split(",")):
        reqs = generator.draw_requests(cell.traffic, cell.platform, rate,
                                       args.seconds, args.seed, 100 + k)
        assert_buckets(cell, windows, reqs)
        problems = make_problems(cell, windows, reqs, args.seed, 100 + k)
        recs, t0, end, delta = measure(cell, svc, fe, problems,
                                       args.seconds, False, windows,
                                       compiled)
        lat = [r.latency for r in recs]
        third = max(len(recs) // 3, 1)
        done_in = sum(r.done is not None and r.done <= end for r in recs)
        line = {
            "sweep_rate_hz": rate, "requests": len(recs),
            "offered_per_s": len(recs) / args.seconds,
            "completed_per_s": done_in / args.seconds,
            "shed": sum(r.shed for r in recs),
            "backlog_at_end": len(recs) - done_in,
            "p50_ms": 1e3 * nearest_rank(lat, 0.5),
            "p95_ms": 1e3 * nearest_rank(lat, 0.95),
            "p95_first_third_ms": 1e3 * nearest_rank(lat[:third], 0.95),
            "p95_last_third_ms": 1e3 * nearest_rank(lat[-third:], 0.95),
            "queue_peak": delta.get("fe_queue_peak"),
            "drains": delta.get("drains"),
            "tier2_checked": delta.get("tier2_checked"),
            "xla_compiles": delta.get("xla_compiles")}
        print(json.dumps(line), flush=True)
