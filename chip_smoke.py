#!/usr/bin/env python3
"""Smoke run of the served matcher path on one TPU chip.

    python3 chip_smoke.py

Drives the path a user calls — ``MatcherService`` submit/drain, then the
``IMMSchedScheduler`` inside the simulator — on the paper's cloud
platform (128 engines on an 8×16 NoC) with the library-default swarm
(64 particles, 4 epochs, 12 inner steps), so the fused Pallas kernels run
compiled on the chip. It exits non-zero, before printing any result,
when JAX finds no TPU or the kernel backend does not resolve to
``pallas``: there is no CPU, ``ref`` or ``interpret`` fallback.

Phases (any failure exits non-zero):

1. **Device and backend**: platform ``tpu``, backend ``pallas``.
2. **Service**, quantized and float: 8 requests (unet, pnasnet and
   nasnet windows on the free array and on half-busy arrays) as a cold
   burst (all Tier 2), the same burst again (a Tier-0 revalidation must
   serve some, and every request the cold burst solved is solved again)
   and the burst with one engine swapped per request (a Tier-1 rebase
   must serve some). Every served mapping passes a plain numpy check;
   the same problems and keys run through the ``ref`` suite, and
   ``pallas`` must find at least as many. The pnasnet and nasnet windows
   contain odd cycles and the mesh is bipartite, so no mapping of them
   exists: "not found" is the right answer there, and a served mapping
   would fail the check.
3. **Scheduler**: a short bursty scenario with urgent arrivals in
   ``matcher_mode="real"``: not truncated, no allocation conflicts, and
   the fused epoch kernels ran on ``pallas``.

Lines before the last are a smoke log, not measurements. The last line
is one JSON object naming the device.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORKLOADS = ("unet", "pnasnet", "nasnet")


class SmokeFailure(Exception):
    """A phase found the program wrong; the message says what."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check_device():
    """Phase 1: the chip and the kernel suite this run must use."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: jax.devices() reports platform "
            f"{devices[0].platform!r} ({len(devices)} device(s))")
    from repro.kernels.backend import resolve_backend_name
    name = resolve_backend_name()
    if name != "pallas":
        raise SmokeFailure(f"kernel backend resolves to {name!r}, not "
                           f"'pallas'")
    return devices


# ---------------------------------------------------------------------------
# Phase 2: the service
# ---------------------------------------------------------------------------

def cloud_requests():
    """The 8 (workload, free-engine mask) requests of the service phase:
    each window on the free array, and on three half-busy arrays (left
    columns, right columns, top rows of the 8×16 NoC)."""
    import numpy as np
    from repro.accel.platform import CLOUD
    rows, cols = CLOUD.noc_rows, CLOUD.noc_cols
    r, c = np.divmod(np.arange(CLOUD.engines), cols)
    full = np.ones(CLOUD.engines, bool)
    left, right, top = c < cols // 2, c >= cols // 2, r < rows // 2
    return [("unet", full), ("pnasnet", full), ("nasnet", full),
            ("unet", left), ("pnasnet", left), ("nasnet", right),
            ("unet", top), ("nasnet", top)]


def window(name: str):
    """Query DAG of the workload's first 4-stage window on CLOUD."""
    from repro.accel.platform import CLOUD
    from repro.core.preemptible_dag import build_preemptible_dag
    from repro.workloads.zoo import get_workload
    return build_preemptible_dag(
        [(0, get_workload(name), 0)],
        tile_capacity_macs=CLOUD.engine_tile_capacity_macs(),
        window_stages=4).graph


def check_mapping(M, query, target) -> None:
    """Plain numpy check of one served mapping: one engine per tile, no
    engine twice, only engines the mask allows, every query edge
    covered."""
    import numpy as np
    from repro.core.graphs import type_compatibility
    M = np.asarray(M, dtype=np.int64)
    if M.shape != (query.n, target.n):
        raise SmokeFailure(f"mapping shape {M.shape} for a "
                           f"{query.n}x{target.n} problem")
    if not (M.sum(axis=1) == 1).all():
        raise SmokeFailure("a tile is not mapped to exactly one engine")
    if (M.sum(axis=0) > 1).any():
        raise SmokeFailure("an engine is used twice")
    qa, ga = np.asarray(query.adj) != 0, np.asarray(target.adj) != 0
    allowed = (type_compatibility(query.types, target.types).astype(bool)
               & (qa.sum(1)[:, None] <= ga.sum(1)[None, :])
               & (qa.sum(0)[:, None] <= ga.sum(0)[None, :]))
    if (M.astype(bool) & ~allowed).any():
        raise SmokeFailure("a tile sits on an engine its mask forbids")
    img = M.argmax(axis=1)
    u, v = np.nonzero(qa)
    if not ga[img[u], img[v]].all():
        raise SmokeFailure("a query edge is not covered by the mapping")


def swap_one_engine(free, used):
    """The free mask with one engine swapped: a free engine the served
    mapping left unused trades places with the busy engine next to it
    (column positions of every other engine stay put); on a fully free
    array the highest unused engine goes busy."""
    free = free.copy()
    unused = [e for e in range(len(free))[::-1] if free[e] and e not in used]
    for e in unused:
        for nb in (e + 1, e - 1):
            if 0 <= nb < len(free) and not free[nb]:
                free[e], free[nb] = False, True
                return free
    free[unused[0]] = False
    return free


def serve(svc, problems, keys):
    """One burst through submit/drain; returns the results in order."""
    from repro.accel.target_graph import free_engine_signature
    for (name, free, query, target), key in zip(problems, keys):
        sig = free_engine_signature(free)
        svc.submit(query, target, key=key, workload_key=(name, sig),
                   engine_sig=sig)
    return svc.drain()


def service_phase(cfg, seed: int = 0):
    """Phase 2 for one ``PSOConfig``: three bursts on ``pallas`` and on
    ``ref``; returns a per-burst summary."""
    import jax
    import numpy as np
    from repro.accel.platform import CLOUD
    from repro.accel.target_graph import free_engine_graph
    from repro.core.service import MatcherService

    queries = {name: window(name) for name in WORKLOADS}
    base = [(name, free, queries[name], free_engine_graph(CLOUD, free))
            for name, free in cloud_requests()]
    keys = list(jax.random.split(jax.random.PRNGKey(seed), len(base)))
    suites = {"pallas": MatcherService(cfg.replace(backend="pallas")),
              "ref": MatcherService(cfg.replace(backend="ref"))}
    summary = {}
    solved = []
    bursts = {"cold": base, "repeat": base}
    for burst, want_tier in (("cold", 2), ("repeat", 0), ("swapped", 1)):
        problems = bursts[burst]
        found = {}
        for suite, svc in suites.items():
            t0 = time.perf_counter()
            results = serve(svc, problems, keys)
            wall = time.perf_counter() - t0
            tiers = [res.tier for res in results]
            for (_, _, query, target), res in zip(problems, results):
                if res.found:
                    check_mapping(res.mapping, query, target)
            found[suite] = sum(res.found for res in results)
            log(f"{'quantized' if cfg.quantized else 'float'} {burst} "
                f"{suite}: found {found[suite]}/{len(results)}, tiers "
                f"{tiers}, wall {wall:.3f}s (smoke run)")
            if suite != "pallas":
                continue
            if burst == "cold":
                solved = [res.found for res in results]
                if tiers != [2] * len(tiers) or not any(solved):
                    raise SmokeFailure(f"cold burst: tiers {tiers}, "
                                       f"found {solved}")
            elif want_tier not in tiers or (burst == "repeat" and any(
                    ok and not r.found for r, ok in zip(results, solved))):
                raise SmokeFailure(
                    f"{burst} burst: tiers {tiers}, found "
                    f"{[r.found for r in results]}, cold found {solved}")
            summary[burst] = {"tiers": tiers, "wall_s": wall}
            if burst == "cold":
                # one engine swapped per request, away from the engines
                # the pallas path served it on
                bursts["swapped"] = []
                for (name, free, query, target), res in zip(base, results):
                    used = set(target.weights[np.argmax(res.mapping, 1)]
                               .astype(int)) if res.found else set()
                    free2 = swap_one_engine(free, used)
                    bursts["swapped"].append(
                        (name, free2, query, free_engine_graph(CLOUD, free2)))
        if found["pallas"] < found["ref"]:
            raise SmokeFailure(f"{burst} burst: pallas found "
                               f"{found['pallas']}, ref {found['ref']}")
    stats = suites["pallas"].stats_dict()
    if stats["epoch_backend"] != "pallas":
        raise SmokeFailure(f"service ran {stats['epoch_backend']!r}")
    return summary


# ---------------------------------------------------------------------------
# Phase 3: the scheduler
# ---------------------------------------------------------------------------

SCENARIO = {
    "name": "cloud-smoke-burst-urgent", "seed": 7, "horizon": 1.0,
    "streams": [
        {"arrival": {"kind": "burst", "rate_hz": 16, "burst_size": 4,
                     "burst_frac": 0.5},
         "workload": {"kind": "mixed_burst", "easy": "simple",
                      "hard": "middle", "hard_frac": 0.5, "burst_size": 4},
         "urgency": {"kind": "never"},
         "deadline": {"kind": "slack"}},
        {"arrival": {"kind": "poisson", "rate_hz": 10},
         "workload": {"kind": "uniform", "complexity": "middle"},
         "urgency": {"kind": "always"},
         "deadline": {"kind": "slack", "urgent_slack": 1.25}},
    ],
}


def scheduler_phase(cfg):
    """Phase 3: IMMSched on a bursty scenario with urgent arrivals."""
    from repro.accel.platform import CLOUD
    from repro.sched import build_scenario
    from repro.sched.schedulers import IMMSchedScheduler
    from repro.sched.simulator import SimConfig, Simulator

    scenario = build_scenario(SCENARIO)
    sim = Simulator(SimConfig(platform=CLOUD, matcher_mode="real",
                              pso_cfg=cfg, validate=True),
                    IMMSchedScheduler())
    t0 = time.perf_counter()
    result = sim.run(scenario)
    wall = time.perf_counter() - t0
    ms = result.matcher_stats
    log(f"scheduler: {len(scenario.tasks)} arrivals, "
        f"{ms.get('sched_matcher_decisions')} matcher decisions, "
        f"epoch launches {ms.get('epoch_fused_launches')}/"
        f"{ms.get('epoch_finish_launches')}, wall {wall:.1f}s (smoke run)")
    if result.truncated:
        raise SmokeFailure("scheduler run was truncated")
    if result.alloc_conflicts != 0:
        raise SmokeFailure(f"{result.alloc_conflicts} allocation conflicts")
    if ms.get("epoch_backend") != "pallas":
        raise SmokeFailure(f"scheduler ran {ms.get('epoch_backend')!r}")
    if not (ms.get("epoch_fused_launches", 0) > 0
            and ms.get("epoch_finish_launches", 0) > 0):
        raise SmokeFailure("no fused epoch launch ran in the scheduler")
    return {"arrivals": len(scenario.tasks),
            "decisions": ms.get("sched_matcher_decisions")}


def main() -> int:
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        devices = check_device()
        from repro.core import persist
        from repro.core.pso import PSOConfig
        log(f"compile cache: {persist.enable_compilation_cache()}")
        t0 = time.perf_counter()
        for quantized in (True, False):
            service_phase(PSOConfig(quantized=quantized))
        scheduler_phase(PSOConfig())
        log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    except (SmokeFailure, ImportError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
