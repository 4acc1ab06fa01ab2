"""Evaluation metrics (paper §4.1.4): Speedup, LBT, Energy efficiency."""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from repro.accel.platform import Platform
from repro.sched.simulator import SimConfig, SimResult, Simulator
from repro.sched.schedulers import get_scheduler
from repro.sched.tasks import Scenario, make_scenario


def run_all(scenario: Scenario, platform: Platform,
            schedulers: Sequence[str],
            matcher_mode: str = "analytic") -> Dict[str, SimResult]:
    out = {}
    for name in schedulers:
        cfg = SimConfig(platform=platform, matcher_mode=matcher_mode)
        out[name] = Simulator(cfg, get_scheduler(name)).run(scenario)
    return out


def speedup_table(results: Dict[str, SimResult],
                  ours: str = "immsched") -> Dict[str, float]:
    """Speedup of ``ours`` vs each baseline: ratio of mean total task
    latency (scheduling + queueing + execution), following IsoSched."""
    base = results[ours].avg_total_latency
    return {name: r.avg_total_latency / max(base, 1e-12)
            for name, r in results.items() if name != ours}


def energy_efficiency(results: Dict[str, SimResult],
                      ours: str = "immsched") -> Dict[str, float]:
    """Improvement in per-task work energy (exec + scheduling) of ``ours``
    vs each baseline — throughput per joule, following the paper."""
    mine = results[ours].work_energy_per_task
    return {name: r.work_energy_per_task / max(mine, 1e-18)
            for name, r in results.items() if name != ours}


def matcher_service_stats(results: Dict[str, SimResult]
                          ) -> Dict[str, Dict[str, float]]:
    """Online matcher-service counters per scheduler: compile-cache and
    warm-start hit rates, per-tier pipeline counters, and epochs saved by
    early exit. Schedulers without any matching state (LTS baselines)
    report an empty dict; IsoSched reports its host memo counters."""
    return {name: dict(r.matcher_stats) for name, r in results.items()
            if r.matcher_stats}


def pipeline_tier_rates(result: SimResult) -> Dict[str, float]:
    """Per-tier serve rates of the tiered matcher pipeline for one run.

    Combines the service's real counters (``tier{0,1,2}_hits``, from
    ``matcher_mode="real"`` launches) with the scheduler's analytic tier
    decisions (``sched_tier{0,1,2}_decisions``, charged in every mode) so
    the decision mix is inspectable regardless of matcher mode."""
    ms = result.matcher_stats
    out: Dict[str, float] = {}
    sched_total = sum(ms.get(f"sched_tier{i}_decisions", 0)
                      for i in range(3))
    for i in range(3):
        out[f"tier{i}_hits"] = ms.get(f"tier{i}_hits", 0)
        d = ms.get(f"sched_tier{i}_decisions", 0)
        out[f"sched_tier{i}_decisions"] = d
        out[f"sched_tier{i}_rate"] = d / max(sched_total, 1)
    calls = ms.get("calls", 0)
    out["revalidated_rate"] = ms.get("revalidated_rate", 0.0)
    out["calls"] = calls
    # fused pre-prune accounting: real sweeps observed by the service and
    # the analytic latency the scheduler charged Tier-2 decisions for it
    out["avg_prune_sweeps"] = ms.get("avg_prune_sweeps", 0.0)
    out["sched_prune_launches"] = ms.get("sched_prune_launches", 0)
    out["sched_prune_wall_s"] = ms.get("sched_prune_wall_s", 0.0)
    # Tier-1 calibration: observed rebase outcomes feeding the predictor
    out["sched_tier1_calib_hits"] = ms.get("sched_tier1_calib_hits", 0)
    out["sched_tier1_calib_trials"] = ms.get("sched_tier1_calib_trials", 0)
    return out


def warm_restart_stats(result: SimResult) -> Dict[str, float]:
    """Warm-restart persistence counters for one run.

    Groups the restart-path observables: how many scheduler-process
    kill/restart events the run saw, what a restore brought back
    (carries / similarity entries / predictor posteriors), and the AOT
    executable-cache counters — ``jit_traces`` is the headline: a warm
    restart that re-traced nothing keeps it at 0 for the restarted
    process. All keys default to 0 for schedulers without a service."""
    ms = result.matcher_stats
    keys = ("restart_count", "restart_restored_carries",
            "restart_restored_sim_entries",
            "restart_restored_posterior_buckets",
            "restart_restored_state_sigs", "restart_snapshots_saved",
            "restart_boot_restores",
            "jit_traces", "aot_cache_hits", "aot_cache_misses",
            "aot_exports", "aot_export_failures", "aot_call_fallbacks",
            "snapshot_saves", "snapshot_restores",
            "snapshot_stale_skipped")
    return {k: ms.get(k, 0) for k in keys}


def frontend_stats(result: SimResult) -> Dict[str, float]:
    """Async front-end counters for one run (``core.service``'s
    ``AsyncServiceFrontEnd``): admission-control outcomes (admitted vs
    shed vs forced drains under the block policy), drain trigger
    reasons (deadline-slack crossing / batch class full / manual flush),
    and queue-depth / waiting-time observables. All keys default to 0
    for runs that never attach a front end."""
    ms = result.matcher_stats
    keys = ("fe_submitted", "fe_admitted", "fe_shed", "fe_forced_drains",
            "fe_drains", "fe_drain_deadline", "fe_drain_batch_full",
            "fe_drain_flush", "fe_queue_peak", "fe_wait_s")
    return {k: ms.get(k, 0) for k in keys}


def transfer_stats(result: SimResult) -> Dict[str, float]:
    """Host-sync census of one run's matcher service (the
    device-resident drain pipeline): drain rounds, blocking device→host
    fetches with their payload bytes and blocked wall time, the bytes
    Tier-2 swarm launches fetched, launches that donated their carry
    buffers, and device-carry-pool activity.
    ``host_syncs_per_drain`` is the pipeline's budget observable — ~1 on
    all-warm drain traffic. Keys default to 0 for analytic runs that
    never touch a live service."""
    ms = result.matcher_stats
    keys = ("drains", "host_syncs", "host_syncs_per_drain",
            "host_bytes_transferred", "host_sync_wall_s",
            "tier2_fetch_bytes", "donated_launches", "pool_puts",
            "pool_writes", "pool_gathers", "pool_live_rows")
    return {k: ms.get(k, 0) for k in keys}


def latency_bound_throughput(scheduler_name: str, platform: Platform,
                             complexity: str, *,
                             hit_target: float = 0.95,
                             horizon: float = 1.0,
                             lo: float = 1.0, hi: float = 4096.0,
                             iters: int = 9, seed: int = 0) -> float:
    """Max Poisson arrival rate (QPS) sustaining ≥ ``hit_target`` urgent
    deadline hit-rate — binary search over λ (paper: LBT = 1/λ*)."""

    def ok(rate: float) -> bool:
        sc = make_scenario(complexity, rate_hz=rate, horizon=horizon,
                           seed=seed)
        if not sc.tasks:
            return True
        cfg = SimConfig(platform=platform, matcher_mode="analytic")
        res = Simulator(cfg, get_scheduler(scheduler_name)).run(sc)
        finished_frac = res.finished / max(res.total, 1)
        return (res.urgent_hit_rate >= hit_target
                and finished_frac >= hit_target)

    if not ok(lo):
        # even the lowest probed rate misses the target: the sustainable
        # rate is below the search bracket, not AT its lower edge —
        # returning `lo` here would report an unsustainable rate as LBT
        return 0.0
    for _ in range(iters):
        mid = (lo * hi) ** 0.5          # geometric bisection
        if ok(mid):
            lo = mid
        else:
            hi = mid
    return lo
