"""Online matcher service: a tiered revalidate → rebase → swarm pipeline.

``pso.match`` alone is a batch API: every new (n, m) query/target shape
triggers an XLA recompile (seconds) and every call restarts the swarm from
the cold uniform prior — the opposite of what an *online* scheduler needs
when tasks arrive unpredictably at microsecond granularity. The
``MatcherService`` turns it into a service:

  * **Shape classes** — query/target problems are bucketed to padded
    ``(n_pad, m_pad)`` classes via ``preemptible_dag.pad_problem`` (dummy
    tiles pinned to dummy PEs, semantics preserved), so repeat arrivals of
    any size within a bucket reuse one compiled executable.
  * **Bounded compile LRU** — one jit wrapper per (bucket, config), held in
    an LRU of ``cache_capacity`` entries; evicting an entry drops its
    executable. Repeat arrivals never recompile.
  * **Warm starts** — the final global-controller state ``(S*, f*, S̄)`` of
    each call is remembered in a two-level :class:`CarryStore`: an *exact*
    content-keyed LRU plus a *similarity* index keyed by
    (query digest, bucket, free-engine signature) for platform-state
    drift.
  * **Early exit** — the service enables ``cfg.early_exit`` so easy
    matches stop scanning epochs once a feasible mapping clears the
    fitness bound (1 epoch instead of T on planted instances).

**The tiered decision pipeline.** ``drain`` flushes every same-bucket
request through three stages, so a mixed easy/hard burst costs one cheap
revalidation launch plus a swarm sized to the hard subset — strictly no
worse than sequential, and far better than the uniform batch that pays
max-epochs × B whenever one hard problem rides in a burst of easy ones:

  * **Tier 0 — batched revalidation.** All requests with a stored exact
    carry are re-validated in ONE ``pso.revalidate_batch`` launch: one
    structured projection + feasibility check per problem, no epochs.
    Hits are served immediately at revalidation cost.
  * **Tier 1 — similarity rebase.** Tier-0 misses (and cold requests)
    whose workload matches a *similar* platform state — same query
    digest, nearest free-engine set by bitmask overlap — are re-run
    through the same revalidation kernel with the neighbour's carry,
    which ``pso.rebase_carry`` projects onto the new compatibility mask.
    A hit stores the rebased carry under this problem's exact key (next
    arrival is a Tier-0 hit); the verified mapping is feasibility-checked
    against the actual problem, so a rebased carry can never yield an
    infeasible mapping marked found.
  * **Tier 2 — swarm.** Only the residual misses launch the full batched
    swarm (``pso.match_batch``), warm-seeded with their failed exact
    carry or the rebased neighbour consensus (f* reset to -inf: fitness
    is not transferable across platform states, direction is).

Batch launches are padded to a small set of classes (``batch_classes``)
that joins the compile-cache key; pad slots are filled with a *trivial
pre-finished problem* whose carry validates in epoch 0, so padding never
re-burns a real problem's epoch budget (its only cost is the slot width).

Per-tier statistics (launches / problems checked / hits) are exported
via ``stats`` / ``stats_dict()`` and surfaced by ``sched.metrics``
through ``SimResult.matcher_stats``.

**Tracing.** The served path writes spans into the profiler's trace with
``jax.profiler.TraceAnnotation``; each is a no-op (1–2 µs) unless a
trace is active. ``immsched.drain`` covers one front-end drain round
(args ``drain``, ``reason``, ``requests``); inside it
``immsched.prepare`` covers each request's ``_prepare`` (``rid``),
``immsched.dispatch`` each tier launch's build and enqueue (``tier``,
``B``, ``bclass``), ``immsched.fetch`` each blocking ``_sync_fetch`` and
``immsched.apply`` each launch's result handling (``tier``). Every span
of a front-end drain carries its ``drain`` number, which the front end
passes to ``submit`` and each request carries to the launches that
serve it. The tier programs are
jitted as ``immsched_swarm``, ``immsched_swarm_batch`` and
``immsched_revalidate``, so their XLA modules read ``jit_immsched_*``
(an executable restored from the AOT cache reads ``jit_call_exported``).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time
import warnings
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.accel.target_graph import signature_bits
from repro.checkpoint.manager import CheckpointManager
from repro.core import persist, pso
from repro.core.graphs import (Graph, compatibility_mask,
                               topological_relabel)
from repro.core.matcher import (MatchResult, build_distributed_match,
                                build_distributed_match_batch,
                                build_distributed_revalidate_batch,
                                collect_batch_results, collect_result,
                                collect_served_results)
from repro.core.preemptible_dag import pad_problem
from repro.kernels import backend as kernel_backend
from repro.kernels import pallas_compat


# process-global latch: the export-drops-donation degradation warning
# fires at most once however many services a process builds
_DONATION_EXPORT_WARNED: List[bool] = []


def _span(name: str, **args) -> TraceAnnotation:
    """Profiler span ``immsched.<name>`` with the ``args`` that are set;
    a no-op unless a trace is active."""
    return TraceAnnotation("immsched." + name,
                           **{k: v for k, v in args.items() if v is not None})


@functools.lru_cache(maxsize=1)
def _default_key() -> np.ndarray:
    """``PRNGKey(0)`` as a host array, for requests that bring no key:
    host keys stack with numpy at dispatch, with no device op."""
    key = np.asarray(jax.random.PRNGKey(0))
    key.flags.writeable = False
    return key


def _tree_nbytes(tree) -> int:
    """Payload bytes of a fetched host pytree."""
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree_util.tree_leaves(tree)))


def _round_up(v: int, mult: int) -> int:
    mult = max(mult, 1)
    return ((v + mult - 1) // mult) * mult


def shape_bucket(n: int, m: int, n_multiple: int = 8,
                 m_multiple: int = 16) -> Tuple[int, int]:
    """Stable padded shape class for an (n, m) matching problem.

    The target bucket must leave room for the ``n_pad - n`` dummy PEs that
    ``pad_problem`` pins the dummy query tiles to.
    """
    n_pad = _round_up(max(n, 1), n_multiple)
    m_pad = _round_up(max(m, 1) + (n_pad - n), m_multiple)
    return n_pad, m_pad


@dataclasses.dataclass
class TierStats:
    """Counters for one pipeline stage."""
    launches: int = 0                # jit dispatches this tier issued
    checked: int = 0                 # real problems examined
    hits: int = 0                    # requests served by this tier

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.checked, 1)


@dataclasses.dataclass
class ServiceStats:
    """Cumulative counters for one ``MatcherService`` incarnation.

    Counters cover the compile LRU, warm-start stores, per-tier pipeline
    activity, the fused pre-prune observable the scheduler calibrates
    against, and the warm-restart persistence layer (``jit_traces`` /
    ``aot_*`` / ``snapshot_*`` / ``restored_*``). Exported flat — plus
    derived rates — by ``MatcherService.stats_dict()``; counters reset
    with the process (a restart starts a fresh incarnation, which is
    exactly what the restart benchmarks measure)."""
    calls: int = 0
    compile_cache_hits: int = 0      # bucket already had an executable
    compile_cache_misses: int = 0    # new bucket → jit compile
    compile_evictions: int = 0
    warm_hits: int = 0               # exact carry found for the call
    warm_misses: int = 0
    warm_evictions: int = 0
    epochs_run: int = 0              # total epochs actually executed
    epochs_budgeted: int = 0         # cfg.epochs × calls
    epoch_fused_launches: int = 0    # swarm dispatches whose epochs ran
                                     # through the fused epoch kernel
                                     # (KernelBackend.epoch_fused_batch)
    epoch_finish_launches: int = 0   # swarm dispatches whose epoch
                                     # epilogue ran through the fused
                                     # tail (KernelBackend.epoch_finish)
    epoch_finish_problems: int = 0   # problems those epilogues covered
                                     # (batch dispatches count B each)
    found: int = 0
    batch_launches: int = 0          # swarm (Tier-2) batch executions
    coalesced_requests: int = 0      # requests served in a shared launch
    batch_problems: int = 0          # real problems through the swarm path
    batch_slots: int = 0             # padded swarm batch slots launched
    carry_fastpath_hits: int = 0     # requests served by revalidation only
                                     # (0 epochs: Tier 0, Tier 1, or the
                                     # in-kernel fast path)
    pad_slots_frozen: int = 0        # pad slots pre-finished from epoch 0
    prune_problems: int = 0          # real problems that ran the pre-prune
    prune_sweeps: int = 0            # total fused prune iterations executed
    sim_lookups: int = 0             # similarity-store nearest() queries
    sim_neighbor_hits: int = 0       # queries that found a neighbour carry
    sim_evictions: int = 0
    # -- warm-restart persistence (AOT executable cache + snapshots) ----
    jit_traces: int = 0              # Python-level jit traces this process
                                     # actually ran (the cold-start cost a
                                     # warm restart must NOT pay: a
                                     # restored burst asserts == 0)
    aot_cache_hits: int = 0          # executables deserialized from disk
    aot_cache_misses: int = 0        # persistence on, but no blob on disk
    aot_exports: int = 0             # executables serialized to disk
    aot_export_failures: int = 0     # export unsupported → plain jit
    aot_call_fallbacks: int = 0      # deserialized blob rejected the call
                                     # signature → live re-trace
    snapshot_saves: int = 0
    snapshot_restores: int = 0       # successful state restores
    snapshot_stale_skipped: int = 0  # version/digest drift → ignored
    snapshot_skipped_keys: int = 0   # entries with unencodable keys
    restored_carries: int = 0        # exact carries loaded by restore
    restored_sim_entries: int = 0    # similarity entries loaded by restore
    # -- async front end (AsyncServiceFrontEnd) ------------------------
    fe_submitted: int = 0            # requests offered to the front end
    fe_admitted: int = 0             # requests accepted into the queue
    fe_shed: int = 0                 # rejected by admission control
    fe_forced_drains: int = 0        # block-policy drains to make room
    fe_drains: int = 0               # total front-end drain rounds
    fe_drain_deadline: int = 0       # rounds fired by slack crossing
    fe_drain_batch_full: int = 0     # rounds fired by a full batch class
    fe_drain_flush: int = 0          # rounds fired by explicit flush
    fe_queue_peak: int = 0           # max observed queue depth
    fe_wait_s: float = 0.0           # total queue-wait time (admit→drain)
    # -- host-sync census (device-resident drain pipeline) --------------
    drains: int = 0                  # drain rounds that flushed requests
    host_syncs: int = 0              # blocking device→host fetches
                                     # (one per pipeline stage under the
                                     # pipelined drain; one per launch
                                     # under the serial arm)
    host_bytes_transferred: int = 0  # payload bytes those fetches moved
    host_sync_wall_s: float = 0.0    # wall time spent blocked in fetches
    tier2_fetch_bytes: int = 0       # payload bytes fetched for Tier-2
                                     # swarm launches
    donated_launches: int = 0        # launches that donated their carry
                                     # input buffers to XLA
    tier0: TierStats = dataclasses.field(default_factory=TierStats)
    tier1: TierStats = dataclasses.field(default_factory=TierStats)
    tier2: TierStats = dataclasses.field(default_factory=TierStats)

    @property
    def epochs_saved(self) -> int:
        """Budgeted minus executed epochs (early exit + fast paths)."""
        return self.epochs_budgeted - self.epochs_run

    @property
    def compile_hit_rate(self) -> float:
        """Fraction of calls served by an already-built executable."""
        return self.compile_cache_hits / max(self.calls, 1)

    @property
    def warm_hit_rate(self) -> float:
        """Fraction of calls that found an exact stored carry."""
        return self.warm_hits / max(self.calls, 1)

    @property
    def revalidated_rate(self) -> float:
        """Fraction of calls served without any swarm epoch (all tiers)."""
        return self.carry_fastpath_hits / max(self.calls, 1)

    @property
    def avg_prune_sweeps(self) -> float:
        """Mean fused pre-prune iterations per pruned problem — the
        prune-latency observable the scheduler's analytic cost model is
        calibrated against."""
        return self.prune_sweeps / max(self.prune_problems, 1)

    @property
    def batch_occupancy(self) -> float:
        """Real problems per launched swarm slot (1.0 = no padding waste).

        Vacuously 1.0 when the pipeline served everything without a
        swarm launch — zero launches waste zero pad slots."""
        if self.batch_slots == 0:
            return 1.0
        return self.batch_problems / self.batch_slots

    @property
    def host_syncs_per_drain(self) -> float:
        """Blocking device→host fetches per drain round — the pipelined
        drain's budget is ONE for an all-warm burst (one batched fetch
        for every Tier-0 launch of every bucket group) and at most one
        per engaged tier otherwise. Counts single ``match`` calls too,
        so read it on drain-only traffic for the regression gate."""
        return self.host_syncs / max(self.drains, 1)


@dataclasses.dataclass
class ServiceMatchResult(MatchResult):
    bucket: Tuple[int, int] = (0, 0)
    compile_cache_hit: bool = False
    warm_hit: bool = False
    latency_s: float = 0.0           # wall time of the launches that
                                     # served this request
    batch_size: int = 1              # real problems in the serving launch
    coalesced: bool = False          # served together with other requests
    tier: int = 2                    # pipeline stage that served it:
                                     # 0 revalidate, 1 rebase, 2 swarm


@dataclasses.dataclass
class _PendingRequest:
    """A submitted problem, pre-padded to its shape bucket so ``drain``
    can group by bucket without touching the graphs again."""
    key: jax.Array
    workload_key: object
    order: np.ndarray
    crop: Tuple[int, int]
    bucket: Tuple[int, int]
    Qp: np.ndarray
    Gp: np.ndarray
    maskp: np.ndarray
    engine_sig: Optional[bytes] = None   # free-engine bitmask (Tier-1 key)
    qdigest: str = ""                    # query-content digest (Tier-1 key)
    cdigest: str = ""                    # full-content digest (Tier-0 key)
    drain: Optional[int] = None          # front-end drain number (span tag)


@dataclasses.dataclass(eq=False)
class _PipelineItem:
    """One request flowing through the tiers of a bucket-group pipeline."""
    req: _PendingRequest
    ticket: int
    warm_key: Tuple
    carry: Optional[tuple]           # exact stored carry (Tier-0 input)
    warm_hit: bool
    seed: Optional[tuple] = None     # rebased neighbour carry (Tier-2 seed)
    t0: float = 0.0                  # pipeline intake timestamp
    latency_s: float = 0.0           # intake → end of the serving launch
    result: Optional[ServiceMatchResult] = None


@dataclasses.dataclass(eq=False)
class _LaunchRecord:
    """One dispatched-but-not-fetched launch of the drain pipeline.

    The pipelined drain splits every tier launch into a *dispatch* half
    (build inputs, enqueue the jit call — JAX returns immediately with
    futures) and an *apply* half (consume the fetched host outputs).
    Records carry everything the apply half needs, so all launches of a
    stage can dispatch back-to-back and resolve through ONE batched
    blocking ``device_get``."""
    kind: str                        # "reval" | "swarm"
    bucket: Tuple[int, int]
    items: List[_PipelineItem]
    tier: int
    B: int                           # real problems in the launch
    bclass: int                      # padded batch class dispatched
    compile_hit: bool
    outs: dict                       # device-side output pytree (futures)
    carries: Optional[List] = None   # reval: per-item input carries
    padded: Optional[List] = None    # swarm: padded request list
    miss_sink: Optional[List] = None # reval: where misses are appended
    drain: Optional[int] = None      # front-end drain number (span tag)


class CarryStore:
    """Two-level warm-start store for the tiered pipeline.

    * **exact** — LRU of full content keys (workload key + shapes + a
      digest of Qp/Gp/maskp): a hit means *this exact problem* was solved
      before; its carry feeds Tier 0.
    * **similarity** — LRU keyed by ``(query digest, bucket, engine
      signature)``: entries describe *which platform state* a carry was
      produced on. ``nearest`` returns the stored carry whose free-engine
      bitmask overlaps the query's the most (ties go to the most recently
      stored), feeding Tier 1 rebases under fragmentation drift.

    ``nearest`` probes a **popcount-bucketed index**: entries of one
    (query digest, bucket) group are binned by the popcount of their
    free-engine bitmask, and bins are visited in decreasing order of the
    best overlap they could possibly hold (``min(pop, query_pop)``),
    stopping as soon as the bound cannot beat the best hit found — at
    thousands of stored platform states the probe touches a handful of
    bins instead of scanning the store. The exhaustive linear scan is
    kept as ``_nearest_linear`` (``sim_index=False`` fallback, and the
    oracle the index is property-tested against).

    Popcounts are computed ONCE on host numpy when an entry is ingested
    (``_sim_pop``) — ``put``/``nearest`` never reduce a bit vector per
    stored entry again, so no store operation can turn into a per-entry
    device sync however the bits arrive.

    The store is payload-agnostic (tests store plain ints), but it
    participates in device-carry lifetime management: any stored value
    exposing ``retain``/``release`` (the service's
    :class:`DeviceCarryPool` handles) is retained on insert and released
    when it is overwritten or evicted, so slab rows are reclaimed the
    moment no store references them.
    """

    def __init__(self, capacity: int, sim_capacity: int,
                 stats: ServiceStats, sim_index: bool = True):
        self.capacity = max(int(capacity), 1)
        self.sim_capacity = max(int(sim_capacity), 1)
        self.stats = stats
        self.sim_index = bool(sim_index)
        self._exact: "OrderedDict[Tuple, tuple]" = OrderedDict()
        self._sim: "OrderedDict[Tuple, Tuple[np.ndarray, tuple]]" = \
            OrderedDict()
        # recency sequence per similarity key (== iteration order of
        # ``_sim``): the index's explicit most-recent-wins tiebreaker
        self._sim_seq: Dict[Tuple, int] = {}
        self._seq = 0
        # (qdigest, bucket, bit-length) -> {popcount: OrderedDict[sig]}
        self._sim_buckets: Dict[Tuple, Dict[int, "OrderedDict[bytes, None]"]] \
            = {}
        # per-entry popcount, computed once at ingest (host numpy)
        self._sim_pop: Dict[Tuple, int] = {}

    def __len__(self) -> int:
        return len(self._exact)

    @property
    def sim_entries(self) -> int:
        """Number of entries currently in the similarity store."""
        return len(self._sim)

    @staticmethod
    def _retain(carry) -> None:
        r = getattr(carry, "retain", None)
        if callable(r):
            r()

    @staticmethod
    def _release(carry) -> None:
        r = getattr(carry, "release", None)
        if callable(r):
            r()

    def clear(self) -> None:
        """Drop both stores and the derived popcount index/recency,
        releasing every device-pool carry they referenced."""
        for c in self._exact.values():
            self._release(c)
        for _, c in self._sim.values():
            self._release(c)
        self._exact.clear()
        self._sim.clear()
        self._sim_seq.clear()
        self._sim_buckets.clear()
        self._sim_pop.clear()

    # -- exact tier --------------------------------------------------------

    def get(self, key) -> Tuple[Optional[tuple], bool]:
        """Exact-store lookup → ``(carry, hit)``; refreshes LRU recency
        and counts ``warm_hits``/``warm_misses``."""
        if key in self._exact:
            self._exact.move_to_end(key)
            self.stats.warm_hits += 1
            return self._exact[key], True
        self.stats.warm_misses += 1
        return None, False

    def put(self, key, carry) -> None:
        """Store ``carry`` (a ``(S*, f*, S̄)`` tuple of (n, m)/scalar/
        (n, m) arrays, or a device-pool handle of one) under the exact
        content key, evicting LRU entries beyond ``capacity``."""
        old = self._exact.get(key)
        if old is not None and old is not carry:
            self._release(old)
        if old is not carry:
            self._retain(carry)
        self._exact[key] = carry
        while len(self._exact) > self.capacity:
            _, evicted = self._exact.popitem(last=False)
            self._release(evicted)
            self.stats.warm_evictions += 1

    # -- similarity tier ---------------------------------------------------

    @staticmethod
    def _bits(sig: bytes) -> np.ndarray:
        return np.asarray(signature_bits(sig))

    def put_similar(self, qdigest: str, bucket: Tuple[int, int],
                    sig: bytes, carry) -> None:
        """Store ``carry`` under the similarity key (query digest, shape
        bucket, free-engine signature) and index it by signature
        popcount (computed once, at ingest); refreshes recency for
        most-recent-wins ``nearest`` tiebreaks."""
        key = (qdigest, bucket, sig)
        bits = self._bits(sig)
        prev = self._sim.get(key)
        fresh = prev is None
        if not fresh and prev[1] is not carry:
            self._release(prev[1])
        if fresh or prev[1] is not carry:
            self._retain(carry)
        self._sim[key] = (bits, carry)
        self._sim.move_to_end(key)
        self._seq += 1
        self._sim_seq[key] = self._seq
        if fresh:
            pc = int(bits.sum())
            self._sim_pop[key] = pc
            group = self._sim_buckets.setdefault(
                (qdigest, bucket, bits.shape[0]), {})
            group.setdefault(pc, OrderedDict())[sig] = None
        while len(self._sim) > self.sim_capacity:
            old_key, (old_bits, old_carry) = self._sim.popitem(last=False)
            self._drop_sim_key(old_key, old_bits)
            self._release(old_carry)
            self.stats.sim_evictions += 1

    def _drop_sim_key(self, key: Tuple, bits: np.ndarray) -> None:
        """Remove an evicted similarity entry from the popcount index
        (``bits``: the entry's already-unpacked bit vector; the entry's
        popcount comes from the ingest-time cache, not a recount)."""
        qd, bk, sig = key
        self._sim_seq.pop(key, None)
        pc = self._sim_pop.pop(key, None)
        gkey = (qd, bk, bits.shape[0])
        group = self._sim_buckets.get(gkey)
        if group is None:
            return
        if pc is None:  # pragma: no cover - pre-index entries
            pc = int(bits.sum())
        bin_ = group.get(pc)
        if bin_ is not None:
            bin_.pop(sig, None)
            if not bin_:
                del group[pc]
        if not group:
            del self._sim_buckets[gkey]

    def nearest(self, qdigest: str, bucket: Tuple[int, int], sig: bytes,
                exclude_sig: Optional[bytes] = None
                ) -> Optional[Tuple[bytes, tuple]]:
        """Stored carry of the platform state nearest to ``sig``.

        Nearest = max popcount of the AND of the free-engine bitmasks;
        ties broken toward the smaller symmetric difference, then toward
        the most recently stored entry. Returns ``(stored_sig, carry)``
        or None when no same-workload entry overlaps at all. Served from
        the popcount-bucketed index (identical results to
        ``_nearest_linear`` — property-tested) unless ``sim_index`` is
        off.
        """
        if not self.sim_index:
            return self._nearest_linear(qdigest, bucket, sig, exclude_sig)
        bits = self._bits(sig)
        qpop = int(bits.sum())
        group = self._sim_buckets.get((qdigest, bucket, bits.shape[0]))
        if not group or qpop == 0:
            return None

        def upper_bound(pc: int) -> Tuple[int, int]:
            # best (overlap, -symdiff) any popcount-pc bitmask can score
            ov = min(pc, qpop)
            return ov, -(pc + qpop - 2 * ov)

        best = None
        best_score = (0, float("-inf"), -1)     # (overlap, -symdiff, seq)
        for pc in sorted(group, key=upper_bound, reverse=True):
            ub = upper_bound(pc)
            if ub[0] <= 0 or ub < (best_score[0], best_score[1]):
                break        # bins are bound-sorted: nothing below can win
            for s in group[pc]:
                if s == exclude_sig:
                    continue
                key = (qdigest, bucket, s)
                b, carry = self._sim[key]
                overlap = int((b & bits).sum())
                if overlap <= 0:
                    continue
                score = (overlap, -int((b ^ bits).sum()),
                         self._sim_seq[key])
                if score > best_score:
                    best_score = score
                    best = (s, carry)
        return best

    # -- snapshot support --------------------------------------------------

    def export_state(self) -> Tuple[List[Tuple[Tuple, tuple]],
                                    List[Tuple[Tuple, tuple]]]:
        """Both stores as ``(exact_items, sim_items)`` key/carry lists.

        Items come out in LRU order (least recent first) so an
        ``import_state`` replay reproduces recency — evictions and
        ``nearest`` most-recent-wins tiebreaks behave identically after
        a snapshot/restore round trip. Carries are returned as stored
        (device or host arrays); the snapshot writer converts to numpy.
        """
        exact = [(k, c) for k, c in self._exact.items()]
        sim = [(k, c) for k, (_, c) in self._sim.items()]
        return exact, sim

    def import_state(self, exact_items, sim_items) -> Tuple[int, int]:
        """Replay exported items into this (fresh) store, oldest first.

        Uses the normal ``put``/``put_similar`` paths so the similarity
        popcount index and recency sequence are rebuilt from scratch —
        the snapshot never persists derived index structures, only the
        keys and carries. Returns ``(n_exact, n_sim)`` loaded. Entries
        beyond this store's capacities age out exactly as live puts
        would."""
        for k, c in exact_items:
            self.put(k, c)
        for (qdigest, bucket, sig), c in sim_items:
            self.put_similar(qdigest, bucket, sig, c)
        return len(exact_items), len(sim_items)

    def _nearest_linear(self, qdigest: str, bucket: Tuple[int, int],
                        sig: bytes, exclude_sig: Optional[bytes] = None
                        ) -> Optional[Tuple[bytes, tuple]]:
        """Exhaustive-scan fallback (and the index's test oracle)."""
        bits = self._bits(sig)
        best = None
        best_score = (0, float("-inf"))
        for (qd, bk, s), (b, carry) in self._sim.items():
            if qd != qdigest or bk != bucket or s == exclude_sig:
                continue
            if b.shape != bits.shape:
                continue
            overlap = int((b & bits).sum())
            if overlap <= 0:
                continue
            score = (overlap, -int((b ^ bits).sum()))
            if score >= best_score:     # >=: most recent wins ties
                best_score = score
                best = (s, carry)
        return best


def carry_write_back(Sb, fb, Cb, S, f, C, idx):
    """Write launch outputs into pool slabs in place. ``idx`` is (2, L)
    int32 over the L output slots: row 0 the destination slab row (the
    slab capacity, out of range, for a slot that stores nothing — the
    scatter drops it), row 1 a flag storing f* = -inf instead of the
    slot's f (a rebased Tier-2 seed)."""
    rows, seed = idx[0], idx[1] != 0
    f = jnp.where(seed, -jnp.inf, f.astype(jnp.float32))
    return (Sb.at[rows].set(S.astype(jnp.float32), mode="drop"),
            fb.at[rows].set(f, mode="drop"),
            Cb.at[rows].set(C.astype(jnp.float32), mode="drop"))


def carry_assemble(Sb, fb, Cb, idx, maskb):
    """One launch's stacked ``(S*, f*, S̄)`` inputs: slot b takes slab row
    ``idx[b]``, or the cold prior of ``maskb[b]`` where ``idx[b] < 0``.
    The result is freshly allocated, so the launch may donate it."""
    cold = idx < 0
    rows = jnp.maximum(idx, 0)
    S0, f0, C0 = pso.default_carry_batch(maskb)
    c3 = cold[:, None, None]
    return (jnp.where(c3, S0, jnp.take(Sb, rows, axis=0)),
            jnp.where(cold, f0, jnp.take(fb, rows, axis=0)),
            jnp.where(c3, C0, jnp.take(Cb, rows, axis=0)))


# one program per (slab capacity, n, m, batch class): the slabs are
# donated, so a write never doubles their footprint
_carry_write_back = jax.jit(carry_write_back, donate_argnums=(0, 1, 2))
_carry_assemble = jax.jit(carry_assemble)


class _CarryHandle:
    """Refcounted reference to one slab row of a :class:`DeviceCarryPool`.

    Stored in :class:`CarryStore` in place of a raw carry tuple: each
    store that holds the handle ``retain``\\ s it, and the row is
    returned to the pool's free list when the last reference is
    ``release``\\ d (eviction, overwrite, or ``clear``). ``materialize``
    yields the ``(S*, f*, S̄)`` view lazily — device slices, no host
    sync."""

    __slots__ = ("pool", "shape", "row", "refs")

    def __init__(self, pool: "DeviceCarryPool", shape: Tuple[int, int],
                 row: int):
        self.pool = pool
        self.shape = shape
        self.row = row
        self.refs = 0

    def retain(self) -> None:
        """Count one more store holding this row."""
        self.refs += 1

    def release(self) -> None:
        """Drop one reference; frees the slab row at zero."""
        self.refs -= 1
        if self.refs <= 0 and self.row >= 0:
            self.pool._free(self.shape, self.row)
            self.row = -1

    def materialize(self) -> tuple:
        """The stored ``(S*, f*, S̄)`` as lazy device slices."""
        return self.pool._read(self.shape, self.row)

    def __iter__(self):
        """Duck-type as the carry tuple itself: iterating a handle
        yields the materialized ``(S*, f*, S̄)`` device parts."""
        return iter(self.materialize())

    def __len__(self) -> int:
        return 3


class _LazyCarry:
    """Tuple-shaped view of a pooled carry handed out in results.

    Slicing three device arrays out of the pool costs real dispatch
    time, and most callers never look at ``result.carry`` — so Tier-0
    hits and single-device Tier-2 results hand out this view instead.
    It retains the handle (pinning the slab row even if the store evicts
    the entry later) and slices the parts out only on first access; the
    reference drops when the view is garbage-collected."""

    __slots__ = ("_handle", "_parts")

    def __init__(self, handle: "_CarryHandle"):
        handle.retain()
        self._handle = handle
        self._parts = None

    def materialize(self) -> tuple:
        if self._parts is None:
            # once sliced, the parts reference the slab *value* at this
            # moment (jax arrays are immutable), so the row pin can drop
            self._parts = self._handle.materialize()
            self._handle.release()
            self._handle = None
        return self._parts

    def __iter__(self):
        return iter(self.materialize())

    def __len__(self) -> int:
        return 3

    def __getitem__(self, i):
        return self.materialize()[i]

    def __del__(self):
        h = self._handle
        if h is not None:
            try:
                h.release()
            except Exception:  # pragma: no cover - interpreter teardown
                pass


class DeviceCarryPool:
    """Device-resident slab storage for warm-start carries.

    One growable slab triple per padded shape — ``S``: (cap, n, m),
    ``f``: (cap,), ``C``: (cap, n, m), all float32, all device-resident —
    handing out refcounted :class:`_CarryHandle` rows. Carries move
    between the slabs and a tier launch through one compiled program per
    launch in each direction:

      * ``write_back`` stores chosen output slots of a launch as fresh
        rows with ONE donated in-place program (``carry_write_back``);
        ``put`` is its one-row case, for carries that arrive alone,
      * ``assemble`` builds a launch's stacked carry input with ONE
        program (``carry_assemble``): each slot takes a row, or the cold
        prior computed from the launch's mask,
      * rows are recycled through a free list as store evictions release
        their handles.

    Slabs grow geometrically (``jnp.concatenate`` with a zero block), so
    amortized write cost stays O(row). The pool never syncs to host; the
    persistence layer materializes handles lazily at snapshot-save time.
    """

    def __init__(self, block: int = 32):
        self.block = max(int(block), 1)
        self._slabs: Dict[Tuple[int, int], dict] = {}
        self.puts = 0                # rows written
        self.writes = 0              # write programs run
        self.gathers = 0             # assembly programs run
        # steady-state drains assemble the same row sets every time;
        # caching the device index array saves a host→device transfer
        # dispatch per launch
        self._idx_cache: "OrderedDict[tuple, jax.Array]" = OrderedDict()

    def _slab(self, shape: Tuple[int, int]) -> dict:
        slab = self._slabs.get(shape)
        if slab is None:
            n, m = shape
            cap = self.block
            slab = {"S": jnp.zeros((cap, n, m), jnp.float32),
                    "f": jnp.zeros((cap,), jnp.float32),
                    "C": jnp.zeros((cap, n, m), jnp.float32),
                    "free": list(range(cap - 1, -1, -1)), "cap": cap}
            self._slabs[shape] = slab
        return slab

    def _alloc(self, shape: Tuple[int, int]) -> int:
        """Take a free row, growing the slab when none is left."""
        slab = self._slab(shape)
        if not slab["free"]:
            old = slab["cap"]
            grow = max(old, self.block)
            n, m = shape
            slab["S"] = jnp.concatenate(
                [slab["S"], jnp.zeros((grow, n, m), jnp.float32)])
            slab["f"] = jnp.concatenate(
                [slab["f"], jnp.zeros((grow,), jnp.float32)])
            slab["C"] = jnp.concatenate(
                [slab["C"], jnp.zeros((grow, n, m), jnp.float32)])
            slab["cap"] = old + grow
            slab["free"] = list(range(old + grow - 1, old - 1, -1))
        return slab["free"].pop()

    def write_back(self, S, f, C, slots: Sequence[int],
                   seeds: Sequence[int] = ()) -> List[_CarryHandle]:
        """Store output slots of one launch — ``S``: (L, n, m), ``f``:
        (L,), ``C``: (L, n, m), device or host — as fresh rows with one
        program, and return their (unretained) handles: ``slots`` with
        their own f*, then ``seeds`` with f* = -inf. The whole outputs go
        in; the program picks the slots."""
        shape = (int(S.shape[1]), int(S.shape[2]))
        pos = list(slots) + list(seeds)
        rows = [self._alloc(shape) for _ in pos]
        slab = self._slabs[shape]
        idx = np.zeros((2, S.shape[0]), np.int32)
        idx[0] = slab["cap"]
        idx[0, pos] = rows
        idx[1, list(seeds)] = 1
        slab["S"], slab["f"], slab["C"] = _carry_write_back(
            slab["S"], slab["f"], slab["C"], S, f, C, idx)
        self.puts += len(rows)
        self.writes += 1
        return [_CarryHandle(self, shape, r) for r in rows]

    def put(self, carry: tuple) -> _CarryHandle:
        """Write one ``(S*, f*, S̄)`` carry into a slab row and return its
        (unretained) handle. Accepts device or host arrays; parts are
        cast to the slab's float32."""
        S, f, C = (p[None] if isinstance(p, jax.Array)
                   else np.asarray(p, np.float32)[None] for p in carry)
        return self.write_back(S, f, C, [0])[0]

    def assemble(self, carries: Sequence[Optional[_CarryHandle]],
                 maskb) -> tuple:
        """Stacked ``(S, f, C)`` launch inputs for one batch with mask
        stack ``maskb`` (B, n, m): slot b takes the row of
        ``carries[b]``, or the cold prior of ``maskb[b]`` where it is
        None — one program, all on device. The result is freshly
        allocated, so callers may donate it to a launch."""
        slab = self._slab((int(maskb.shape[1]), int(maskb.shape[2])))
        rows = tuple(-1 if c is None else c.row for c in carries)
        idx = self._idx_cache.get(rows)
        if idx is None:
            # from a numpy array: a plain upload, no conversion program
            idx = jnp.asarray(np.asarray(rows, np.int32))
            self._idx_cache[rows] = idx
            while len(self._idx_cache) > 256:
                self._idx_cache.popitem(last=False)
        self.gathers += 1
        return _carry_assemble(slab["S"], slab["f"], slab["C"], idx, maskb)

    def _read(self, shape: Tuple[int, int], row: int) -> tuple:
        slab = self._slabs[shape]
        return (slab["S"][row], slab["f"][row], slab["C"][row])

    def _free(self, shape: Tuple[int, int], row: int) -> None:
        slab = self._slabs.get(shape)
        if slab is not None:
            slab["free"].append(row)

    @property
    def live_rows(self) -> int:
        """Rows currently referenced by at least one store entry."""
        return sum(s["cap"] - len(s["free"])
                   for s in self._slabs.values())


class MatcherService:
    """Warm-start online wrapper around Algorithm 1.

    Single-device by default; pass ``mesh`` + ``axis_names`` to run each
    bucket's executable as the collective-fused distributed matcher.
    ``tiered=False`` disables the staged pipeline and restores the
    uniform one-swarm-launch-per-batch drain (the PR-2 baseline);
    ``similarity=False`` keeps the pipeline but disables Tier-1 rebases
    (the content-keyed baseline).

    **Warm-restart persistence.** Pass ``persist_dir`` (or set
    ``REPRO_PERSIST_DIR``; pass ``persist_dir=False`` to force
    persistence off even when the env var is set — the cold-restart
    baseline arm) to survive process restarts:

      * ``<persist_dir>/aot/`` — each single-device executable is
        ``jax.export``-serialized on its first trace and lazily
        deserialized on the first compile-LRU miss of a restarted
        process, so the first post-restart burst runs with
        ``stats.jit_traces == 0``. Keys include the resolved kernel
        backend, every ``PSOConfig`` field, bucketing parameters, jax
        version and platform (``config_digest``) — drift is a clean
        miss, never a wrong program. Mesh-sharded executables are not
        exported (the blob pins device topology); they rely on the XLA
        compilation-cache fallback below.
      * ``<persist_dir>/snapshots/`` — ``save_snapshot`` /
        ``restore_snapshot`` persist the :class:`CarryStore` (exact +
        similarity carries; the popcount index is rebuilt on load) and
        the prune-sweep calibration counters through
        :class:`~repro.checkpoint.manager.CheckpointManager` (atomic
        commit, ``keep=snapshot_keep``). Snapshots are versioned and
        digest-validated: a restore against a drifted config is skipped
        cleanly (``snapshot_stale_skipped``), never mis-applied.
      * JAX's persistent compilation cache is turned on (process-global;
        opt out with ``REPRO_JAX_CACHE=0``) at ``JAX_COMPILATION_CACHE_DIR``
        when set, else ``<checkout>/.jax_cache`` (see
        :func:`~repro.core.persist.enable_compilation_cache`), so the
        residual XLA compile of deserialized modules and of the
        non-exportable mesh executables is also served from disk.
    """

    def __init__(self, cfg: Optional[pso.PSOConfig] = None, *,
                 mesh=None, axis_names: Sequence[str] = ("data",),
                 cache_capacity: int = 16, warm_capacity: int = 256,
                 warm_start: bool = True, early_exit: bool = True,
                 n_multiple: int = 8, m_multiple: int = 16,
                 batch_classes: Sequence[int] = (1, 2, 4, 8),
                 tiered: bool = True, similarity: bool = True,
                 sim_capacity: int = 128, sim_index: bool = True,
                 pipelined: bool = True,
                 donate_buffers: Optional[bool] = None,
                 persist_dir: Union[str, bool, None] = None,
                 aot_cache: Optional[bool] = None,
                 snapshot_keep: int = 3):
        cfg = cfg or pso.PSOConfig()
        if early_exit and not cfg.early_exit:
            cfg = cfg.replace(early_exit=True)
        self.cfg = cfg
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.cache_capacity = max(int(cache_capacity), 1)
        self.warm_start = warm_start
        self.n_multiple = n_multiple
        self.m_multiple = m_multiple
        self.batch_classes = tuple(sorted(set(int(b) for b in batch_classes)))
        assert self.batch_classes and self.batch_classes[0] >= 1
        self.tiered = tiered
        self.similarity = similarity
        # pipelined=False restores the legacy serial drain (host-staged
        # carry stacking, dispatch → blocking fetch per launch) — the
        # baseline arm bench_pipeline measures the pipeline against
        self.pipelined = bool(pipelined)
        if donate_buffers is None:
            donate_buffers = pallas_compat.donation_supported()
        self.donate_buffers = bool(donate_buffers)
        self.stats = ServiceStats()
        self._carries = CarryStore(warm_capacity, sim_capacity, self.stats,
                                   sim_index=sim_index)
        self._pool = DeviceCarryPool()
        # per-bucket pre-finished pad carry, pooled once and pinned so
        # padded warm batches stay all-handle (one-gather launch inputs)
        self._pad_handles: Dict[Tuple[int, int], _CarryHandle] = {}
        self._compiled: "OrderedDict[Tuple, object]" = OrderedDict()
        self._pending: List[_PendingRequest] = []
        # -- persistence wiring -------------------------------------------
        # persist_dir: a path enables persistence there; None defers to
        # the REPRO_PERSIST_DIR env var; False forces persistence OFF
        # even when the env var is set (cold-restart baselines must not
        # silently warm up from an operator's persist root).
        if persist_dir is None:
            persist_dir = persist.default_persist_dir()
        self.persist_dir = persist_dir if persist_dir else None
        if aot_cache is None:
            aot_cache = persist.aot_cache_enabled()
        self._aot: Optional[persist.AOTCache] = None
        self._ckpt: Optional[CheckpointManager] = None
        if self.persist_dir:
            if aot_cache:
                self._aot = persist.AOTCache(
                    os.path.join(self.persist_dir, "aot"), self.stats)
            self._ckpt = CheckpointManager(
                os.path.join(self.persist_dir, "snapshots"),
                async_save=False, keep=snapshot_keep)
            persist.enable_compilation_cache()
        if self._aot is not None and self.donate_buffers \
                and not _DONATION_EXPORT_WARNED \
                and not pallas_compat.export_preserves_donation():
            # degrade LOUDLY (once per process): results stay correct,
            # but AOT-restored executables run without the in-place
            # carry update
            _DONATION_EXPORT_WARNED.append(True)
            warnings.warn(
                "jax.export round trips drop donate_argnums on this "
                "toolchain: AOT-cached executables will not update "
                "carry buffers in place (correctness is unaffected). "
                "Pass donate_buffers=False to silence.",
                RuntimeWarning, stacklevel=2)

    @property
    def warm_capacity(self) -> int:
        """Exact warm-start store capacity (entries)."""
        return self._carries.capacity

    def clear_carries(self) -> None:
        """Drop every stored warm-start carry (exact and similarity)."""
        self._carries.clear()

    @property
    def config_digest(self) -> str:
        """Digest guarding everything persisted by this service: resolved
        kernel backend + all ``PSOConfig`` fields + shape-bucketing
        parameters + jax version/platform + mesh-ness. AOT executables
        and snapshots from a process whose digest differs are ignored."""
        return kernel_backend.config_digest(
            self.cfg,
            extra=("svc-v3", jax.__version__, jax.default_backend(),
                   self.n_multiple, self.m_multiple, self.batch_classes,
                   self.mesh is not None))

    # -- caches ------------------------------------------------------------

    def _cache_put(self, cache_key, fn):
        self._compiled[cache_key] = fn
        while len(self._compiled) > self.cache_capacity:
            self._compiled.popitem(last=False)
            self.stats.compile_evictions += 1
        return fn

    def _cache_get(self, cache_key):
        fn = self._compiled.get(cache_key)
        if fn is not None:
            self._compiled.move_to_end(cache_key)
            self.stats.compile_cache_hits += 1
        return fn

    def _count_first_call(self, fn):
        """Wrap a live-jit executable so its lazy first-call trace shows
        up in ``stats.jit_traces`` (the observable the AOT cache zeroes
        out across restarts)."""
        fired: List[int] = []

        def wrapped(*args):
            if not fired:
                fired.append(1)
                self.stats.jit_traces += 1
            return fn(*args)

        return wrapped

    def _resolve_executable(self, cache_key, kind: str,
                            bucket: Tuple[int, int], bclass: int, build):
        """Compile-LRU lookup with the on-disk AOT layer behind it.

        Miss order: (1) in-memory LRU; (2) deserialized ``jax.export``
        blob — runs with NO Python trace; (3) ``build()`` a live jit
        function, which traces on first call and (when exportable and
        persistence is on) serializes itself to disk for the next
        process. Every path lands in the LRU under ``cache_key``."""
        fn = self._cache_get(cache_key)
        if fn is not None:
            return fn
        self.stats.compile_cache_misses += 1
        if self._aot is not None:
            aot_key = f"{kind}-n{bucket[0]}m{bucket[1]}-b{bclass}" \
                      f"-{self.config_digest}"
            loaded = self._aot.load(aot_key, build)
            if loaded is not None:
                self.stats.aot_cache_hits += 1
                return self._cache_put(cache_key, loaded)
            self.stats.aot_cache_misses += 1
            built = build()
            if getattr(built, "aot_exportable", True):
                return self._cache_put(
                    cache_key, self._aot.wrap_exporting(aot_key, built))
            return self._cache_put(cache_key, self._count_first_call(built))
        return self._cache_put(cache_key, self._count_first_call(build()))

    def _executable(self, bucket: Tuple[int, int]):
        """Single-problem swarm executable for one shape bucket, jitted
        as ``immsched_swarm``: its XLA module reads
        ``jit_immsched_swarm``, or ``jit_call_exported`` once restored
        from the AOT cache."""
        def build():
            if self.mesh is None:
                cfg = self.cfg

                def immsched_swarm(key, Q, G, mask, carry0, _cfg=cfg):
                    return pso._match_body(key, Q, G, mask, _cfg, carry0)

                return jax.jit(immsched_swarm)
            return build_distributed_match(bucket, self.mesh, self.cfg,
                                           self.axis_names)

        return self._resolve_executable(bucket, "match", bucket, 1, build)

    def _executable_batch(self, bucket: Tuple[int, int], bclass: int):
        """One swarm executable per (shape bucket, padded batch class),
        jitted as ``immsched_swarm_batch`` (module
        ``jit_immsched_swarm_batch``; ``jit_call_exported`` once
        restored from the AOT cache). On a single device it selects each
        problem's served mapping in the program (``pso.served_outs``) and
        returns no particle trace; the mesh program returns the trace."""
        def build():
            if self.mesh is None:
                cfg = self.cfg

                def immsched_swarm_batch(keys, Qb, Gb, maskb, carry0,
                                         _cfg=cfg):
                    return pso.served_outs(pso._match_batch_body(
                        keys, Qb, Gb, maskb, _cfg, carry0))

                return jax.jit(immsched_swarm_batch,
                               donate_argnums=self._donate_argnums("batch"))
            return build_distributed_match_batch(bucket, self.mesh,
                                                 self.cfg, self.axis_names,
                                                 bclass)

        return self._resolve_executable((bucket, bclass), "batch",
                                        bucket, bclass, build)

    def _executable_reval(self, bucket: Tuple[int, int], bclass: int):
        """Tier-0/1 revalidation executable (no epochs, no keys), jitted
        as ``immsched_revalidate`` (module ``jit_immsched_revalidate``;
        ``jit_call_exported`` once restored from the AOT cache)."""
        def build():
            if self.mesh is None:
                cfg = self.cfg

                def immsched_revalidate(Qb, Gb, maskb, carry0, _cfg=cfg):
                    return pso._revalidate_batch_body(Qb, Gb, maskb, _cfg,
                                                      carry0)

                return jax.jit(immsched_revalidate,
                               donate_argnums=self._donate_argnums("reval"))
            return build_distributed_revalidate_batch(
                bucket, self.mesh, self.cfg, self.axis_names, bclass)

        return self._resolve_executable((bucket, bclass, "reval"), "reval",
                                        bucket, bclass, build)

    def _batch_class(self, k: int) -> int:
        """Smallest padded batch class holding k problems."""
        for c in self.batch_classes:
            if c >= k:
                return c
        return self.batch_classes[-1]

    @staticmethod
    def _warm_key(req: _PendingRequest) -> Tuple:
        """Exact warm starts are only valid for the *same* problem (f*
        values are not comparable across different Q/G), so the key always
        includes the content digest ``_prepare`` computed; the request's
        ``workload_key`` additionally scopes entries to the caller's
        (workload, platform-state) naming."""
        return (req.workload_key, req.Qp.shape[0], req.Gp.shape[0],
                req.cdigest)

    def _get_carry(self, warm_key):
        if not self.warm_start:
            self.stats.warm_misses += 1
            return None, False
        return self._carries.get(warm_key)

    def _put_carry(self, warm_key, carry):
        if self.warm_start:
            self._carries.put(warm_key, carry)

    def _store_carry(self, req: _PendingRequest, warm_key, stored,
                     similar: bool) -> None:
        """Store a fresh carry (a pool handle, or the host carry on a
        mesh service, whose launch outputs carry mesh shardings the
        single-device slabs can't hold) under the exact key, and — when
        the call produced a served decision (``similar``) on a known
        platform state — under the similarity key too, so future drifted
        states can rebase it."""
        self._put_carry(warm_key, stored)
        if similar and self.similarity and req.engine_sig is not None:
            self._carries.put_similar(req.qdigest, req.bucket,
                                      req.engine_sig, stored)

    # -- snapshots ---------------------------------------------------------

    def save_snapshot(self, step: Optional[int] = None,
                      extra: Optional[Dict] = None) -> int:
        """Persist the service's warm state as one atomic checkpoint.

        Saved: every :class:`CarryStore` entry (exact and similarity,
        in LRU order; carries land as one ``.npy`` leaf per array) plus
        the prune-sweep calibration counters
        (``prune_problems``/``prune_sweeps`` — the observable the
        scheduler's analytic cost model reads). NOT saved: compiled
        executables (the AOT cache owns those), transient stats, pending
        requests. ``extra`` (JSON-serializable) rides in the snapshot
        metadata — the scheduler stores its tier-predictor posteriors
        there. Entries whose keys cannot be encoded (non-str/int/bytes/
        tuple workload keys) are skipped and counted
        (``snapshot_skipped_keys``). Returns the committed step number.
        Requires ``persist_dir``."""
        if self._ckpt is None:
            raise RuntimeError("save_snapshot needs persist_dir "
                               "(or REPRO_PERSIST_DIR)")
        exact_items, sim_items = self._carries.export_state()
        arrays: Dict[str, np.ndarray] = {}
        exact_keys, exact_carries = [], []
        for k, c in exact_items:
            try:
                exact_keys.append(persist.encode_key(k))
            except TypeError:
                self.stats.snapshot_skipped_keys += 1
                continue
            # device-pool handles materialize to lazy device slices here;
            # the ONE blocking transfer happens inside carry_leaves
            exact_carries.append(self._carry_tuple(c))
        sim_keys, sim_carries = [], []
        for k, c in sim_items:
            try:
                sim_keys.append(persist.encode_key(k))
            except TypeError:
                self.stats.snapshot_skipped_keys += 1
                continue
            sim_carries.append(self._carry_tuple(c))
        arrays.update(persist.carry_leaves("exact", exact_carries))
        arrays.update(persist.carry_leaves("sim", sim_carries))
        # flat-dict checkpoints must be non-empty for restore_flat to see
        # a committed structure even when no carries are stored yet
        arrays["snapshot.marker"] = np.zeros((), np.int8)
        extras = {
            "format_version": persist.SNAPSHOT_VERSION,
            "config_digest": self.config_digest,
            "exact_keys": exact_keys,
            "sim_keys": sim_keys,
            "calibration": {
                "prune_problems": int(self.stats.prune_problems),
                "prune_sweeps": int(self.stats.prune_sweeps),
            },
            "extra": extra or {},
        }
        if step is None:
            latest = self._ckpt.latest_step()
            step = 0 if latest is None else latest + 1
        self._ckpt.save(step, arrays, extras=extras)
        self._ckpt.wait()
        self.stats.snapshot_saves += 1
        return step

    def restore_snapshot(self, step: Optional[int] = None
                         ) -> Optional[Dict]:
        """Load the newest (or ``step``-th) snapshot into this service.

        Validation before anything is touched: the snapshot's format
        version and ``config_digest`` must match this service's — a
        snapshot written under a different kernel backend, ``PSOConfig``,
        bucketing, jax version or platform is counted in
        ``snapshot_stale_skipped`` and ignored (warm state from a
        drifted config could verify carries that no longer mean the same
        thing). On success the :class:`CarryStore` is rebuilt (recency
        preserved, similarity popcount index reconstructed), the
        prune-sweep calibration counters are re-seeded, and the
        snapshot's ``extra`` dict is returned (``{}`` when none was
        stored). Returns None when nothing (valid) exists to restore.
        Requires ``persist_dir``."""
        if self._ckpt is None:
            raise RuntimeError("restore_snapshot needs persist_dir "
                               "(or REPRO_PERSIST_DIR)")
        try:
            arrays, extras = self._ckpt.restore_flat(step)
        except (OSError, ValueError, KeyError):
            arrays, extras = None, None
        if arrays is None:
            return None
        if extras.get("format_version") != persist.SNAPSHOT_VERSION or \
                extras.get("config_digest") != self.config_digest:
            self.stats.snapshot_stale_skipped += 1
            return None
        exact_keys = [persist.decode_key(k) for k in extras["exact_keys"]]
        sim_keys = [persist.decode_key(k) for k in extras["sim_keys"]]
        exact_carries = persist.carries_from_leaves(
            "exact", arrays, len(exact_keys))
        sim_carries = persist.carries_from_leaves(
            "sim", arrays, len(sim_keys))
        if self.mesh is None:
            # restored carries go straight back to device residency: one
            # pool row per entry, uploaded once; rows free themselves as
            # store replay/evictions release the handles
            exact_carries = [self._pool.put(c) for c in exact_carries]
            sim_carries = [self._pool.put(c) for c in sim_carries]
        n_exact, n_sim = self._carries.import_state(
            list(zip(exact_keys, exact_carries)),
            list(zip(sim_keys, sim_carries)))
        calib = extras.get("calibration", {})
        self.stats.prune_problems += int(calib.get("prune_problems", 0))
        self.stats.prune_sweeps += int(calib.get("prune_sweeps", 0))
        self.stats.snapshot_restores += 1
        self.stats.restored_carries += n_exact
        self.stats.restored_sim_entries += n_sim
        return extras.get("extra", {})

    def verify_snapshot_roundtrip(self, step: Optional[int] = None
                                  ) -> bool:
        """Save a snapshot, restore it into a FRESH twin service, and
        bitwise-compare the warm state — the mid-run round-trip probe
        the invariant fuzzer leans on.

        The twin is built with this service's config (same
        ``config_digest``, so the restore is accepted) but no AOT cache
        (snapshots only; nothing is compiled). Compared: both carry
        stores' key sequences in LRU order, every carry leaf
        (``dtype``/``shape``/bytes — via :meth:`_carry_tuple`, so
        device-pool handles materialize identically on both sides) and
        the prune-sweep calibration counters. Raises ``AssertionError``
        naming the first divergence; returns True when the round trip
        is bitwise clean. Requires ``persist_dir``."""
        step = self.save_snapshot(step=step)
        twin = MatcherService(
            self.cfg, mesh=self.mesh, axis_names=self.axis_names,
            cache_capacity=self.cache_capacity,
            warm_capacity=self._carries.capacity,
            warm_start=self.warm_start, n_multiple=self.n_multiple,
            m_multiple=self.m_multiple,
            batch_classes=self.batch_classes, tiered=self.tiered,
            similarity=self.similarity,
            sim_capacity=self._carries.sim_capacity,
            sim_index=self._carries.sim_index,
            pipelined=self.pipelined,
            donate_buffers=self.donate_buffers,
            persist_dir=self.persist_dir, aot_cache=False)
        restored = twin.restore_snapshot(step=step)
        assert restored is not None, \
            "snapshot round trip: restore rejected its own snapshot"

        def _leaves(svc):
            exact, sim = svc._carries.export_state()
            return ([(k, svc._carry_tuple(c)) for k, c in exact],
                    [(k, svc._carry_tuple(c)) for k, c in sim])

        for store, mine, theirs in zip(("exact", "sim"), _leaves(self),
                                       _leaves(twin)):
            assert [k for k, _ in mine] == [k for k, _ in theirs], \
                f"snapshot round trip: {store} store keys diverged"
            for (key, a), (_, b) in zip(mine, theirs):
                a, b = [tuple(np.asarray(x) for x in c) for c in (a, b)]
                assert len(a) == len(b), \
                    f"snapshot round trip: carry arity for {key!r}"
                for x, y in zip(a, b):
                    assert x.dtype == y.dtype and x.shape == y.shape \
                        and x.tobytes() == y.tobytes(), \
                        f"snapshot round trip: {store} carry for " \
                        f"{key!r} not bitwise equal"
        assert (twin.stats.prune_problems, twin.stats.prune_sweeps) == \
            (self.stats.prune_problems, self.stats.prune_sweeps), \
            "snapshot round trip: calibration counters diverged"
        return True

    # -- matching ----------------------------------------------------------

    def _prepare(self, query: Graph, target: Graph, key, workload_key,
                 engine_sig: Optional[bytes] = None,
                 rid: Optional[int] = None,
                 drain: Optional[int] = None) -> _PendingRequest:
        """Relabel, bucket and pad a problem on the host — the jit call
        uploads Qp/Gp/maskp once; no device→host→device round trip.
        Runs under an ``immsched.prepare`` span carrying ``rid`` and
        ``drain``, the front end's request id and drain number, when
        given; the request keeps ``drain`` for its launches' spans.

        ``engine_sig`` (the free-engine bitmask, see
        ``accel.target_graph.free_engine_signature``) keys the similarity
        store; when omitted it is recovered from a ``(name, sig)``-style
        ``workload_key`` whose last element is bytes — the scheduler's
        existing naming convention."""
        with _span("prepare", rid=rid, drain=drain):
            if key is None:
                key = _default_key()
            if engine_sig is None and isinstance(workload_key, tuple) \
                    and workload_key and isinstance(workload_key[-1], bytes):
                engine_sig = workload_key[-1]
            q, order = topological_relabel(query)
            n, m = q.n, target.n
            mask = compatibility_mask(q, target)
            bucket = shape_bucket(n, m, self.n_multiple, self.m_multiple)
            Qp, Gp, maskp = pad_problem(q.adj, target.adj, mask, *bucket)
            # one hashing pass yields both keys: the query-only digest (the
            # similarity key) is a prefix state of the full content digest
            # (the exact warm key)
            h = hashlib.sha1(np.ascontiguousarray(Qp).tobytes())
            qdigest = h.hexdigest()
            h.update(np.ascontiguousarray(Gp).tobytes())
            h.update(np.ascontiguousarray(maskp).tobytes())
            return _PendingRequest(key=key, workload_key=workload_key,
                                   order=order, crop=(n, m), bucket=bucket,
                                   Qp=Qp, Gp=Gp, maskp=maskp,
                                   engine_sig=engine_sig, qdigest=qdigest,
                                   cdigest=h.hexdigest(), drain=drain)

    def _note_prune(self, problems: int, sweeps: int) -> None:
        """Account the fused pre-prune work a launch reported (the
        ``prune_sweeps`` observable of the match/revalidate kernels)."""
        if self.cfg.prune_mask and problems > 0:
            self.stats.prune_problems += problems
            self.stats.prune_sweeps += int(sweeps)

    def _tiers_active(self) -> bool:
        """Tier 0/1 only exist when the kernel fast path they batch is on
        (otherwise serving at 0 epochs would change semantics)."""
        return (self.tiered and self.warm_start
                and self.cfg.early_exit and self.cfg.carry_fastpath)

    # -- device residency --------------------------------------------------

    def _sync_fetch(self, tree, drain: Optional[int] = None):
        """THE blocking device→host transfer of the drain pipeline.

        Fetches a whole pytree (typically every pending launch's outputs)
        with one ``jax.device_get`` and records it in the host-sync
        census: ``host_syncs`` (count), ``host_bytes_transferred``
        (payload) and ``host_sync_wall_s`` (time spent blocked). Every
        result-consuming path routes through here, so the counters ARE
        the sync budget the transfer-guard test pins. ``drain`` tags its
        ``immsched.fetch`` span."""
        with _span("fetch", drain=drain):
            t0 = time.perf_counter()
            host = jax.device_get(tree)
            self.stats.host_syncs += 1
            self.stats.host_sync_wall_s += time.perf_counter() - t0
        self.stats.host_bytes_transferred += _tree_nbytes(host)
        return host

    def _fetch_tree(self, rec: "_LaunchRecord"):
        """The subset of a launch's outputs its apply step actually
        reads on host. Tier-0 revalidation never looks at the rebased
        ``S*``/``S̄`` planes host-side (hit carries stay pooled on
        device), so skipping them keeps the biggest leaves out of every
        warm fetch. A swarm launch fetches each problem's served mapping,
        the (T, B, N) flags and fitness, the f* trace and the
        per-problem scalars: its S*/S̄ planes stay in the pool. Mesh
        launches fetch everything."""
        if self.mesh is not None:
            return rec.outs
        if rec.kind == "swarm":
            keys = ("mapping", "found", "feasible", "fitness",
                    "f_star_trace", "f_star", "epochs_run",
                    "carry_feasible", "prune_sweeps")
        elif rec.tier == 0:
            keys = ("mapping", "ok", "f_carry", "prune_sweeps")
        else:
            keys = ("mapping", "ok_rebase", "fitness", "S_star", "S_bar",
                    "prune_sweeps")
        return {k: rec.outs[k] for k in keys}

    @staticmethod
    def _carry_tuple(carry) -> tuple:
        """A stored carry as its ``(S*, f*, S̄)`` tuple: device-pool
        handles and lazy result views are materialized (lazy device
        slices, no host sync); plain tuples pass through."""
        if isinstance(carry, (_CarryHandle, _LazyCarry)):
            return carry.materialize()
        return carry

    @property
    def _pooled(self) -> bool:
        """Whether launches take their carries straight from the device
        pool (``DeviceCarryPool.assemble``): the single-device pipelined
        drain. Mesh services and the ``pipelined=False`` arm stage them
        through host numpy (``_stack_carries``)."""
        return self.mesh is None and self.pipelined

    def _stack_carries(self, carries: List) -> tuple:
        """Stacked ``(B, ...)`` carry inputs for one launch of a mesh
        service or the ``pipelined=False`` arm: the legacy host staging
        the pooled path replaced. Each carry part is pulled to host with
        a blocking ``np.asarray`` and re-stacked with numpy. Those
        implicit device→host transfers are what the pooled path
        eliminates, so they are charged to the host-sync census here
        (one sync per device-resident part)."""
        mats = [self._carry_tuple(c) for c in carries]
        stacked = []
        for i in range(3):
            parts = []
            for mat in mats:
                p = mat[i]
                if isinstance(p, jax.Array):
                    t0 = time.perf_counter()
                    p = np.asarray(p)
                    self.stats.host_syncs += 1
                    self.stats.host_sync_wall_s += time.perf_counter() - t0
                    self.stats.host_bytes_transferred += int(p.nbytes)
                parts.append(np.asarray(p))
            stacked.append(np.stack(parts))
        return tuple(stacked)

    def _donate_argnums(self, kind: str) -> Tuple[int, ...]:
        """Argnums a fresh jit build of ``kind`` may donate (empty when
        ``donate_buffers`` is off or the kind's inputs can alias stored
        state — see ``kernels.backend.SERVICE_DONATABLE_ARGNUMS``)."""
        if not self.donate_buffers:
            return ()
        return kernel_backend.donate_argnums_for(kind)

    def match(self, query: Graph, target: Graph,
              key: Optional[jax.Array] = None,
              workload_key=None,
              engine_sig: Optional[bytes] = None) -> ServiceMatchResult:
        """Match ``query`` onto ``target`` through the service caches.

        ``workload_key`` names the (workload, platform-state) class for
        warm-start scoping — e.g. ``(task_name, free_engine_signature)``.
        Results are exactly the unpadded equivalent of a direct
        ``pso.match`` on the same problem. A single call serves warm
        repeats through the in-kernel carry fast path (Tier 0, free
        inside the swarm launch) and attempts a Tier-1 rebase on an
        exact-carry MISS with a similar stored platform state. Unlike
        ``drain``, a failed exact carry goes straight to the swarm —
        probing the similarity store behind it would add a second
        dispatch to every warm single call; batch that traffic through
        ``submit``/``drain`` to get the full pipeline.
        """
        t0 = time.perf_counter()
        self.stats.calls += 1
        self.stats.epochs_budgeted += self.cfg.epochs
        req = self._prepare(query, target, key, workload_key, engine_sig)
        key, bucket = req.key, req.bucket
        order, (n, m) = req.order, req.crop
        Qp, Gp, maskp = req.Qp, req.Gp, req.maskp

        warm_key = self._warm_key(req)
        carry0, warm_hit = self._get_carry(warm_key)
        if carry0 is not None:
            self.stats.tier0.checked += 1

        # Tier 1 (single-call path): exact miss, but a similar platform
        # state is stored — revalidate its rebased carry before swarming.
        seed = None
        if carry0 is None and self._tiers_active() and self.similarity \
                and req.engine_sig is not None:
            item = _PipelineItem(req=req, ticket=0, warm_key=warm_key,
                                 carry=None, warm_hit=False, t0=t0)
            nb = self._lookup_neighbor(item)
            if nb is not None:
                residual = self._launch_revalidate(bucket, [item], [nb],
                                                   tier=1)
                if not residual:
                    res = item.result
                    res.latency_s = time.perf_counter() - t0
                    return res
                seed = item.seed

        hits_before = self.stats.compile_cache_hits
        fn = self._executable(bucket)
        compile_hit = self.stats.compile_cache_hits > hits_before

        if carry0 is None:
            carry0 = self._carry_tuple(seed) if seed is not None \
                else pso.default_carry(jnp.asarray(maskp))
        else:
            carry0 = self._carry_tuple(carry0)

        if self.mesh is None:
            outs = fn(key, Qp, Gp, maskp, carry0)
        else:
            num_shards = int(np.prod([self.mesh.shape[a]
                                      for a in self.axis_names]))
            keys = jax.random.split(key, num_shards)
            outs = fn(keys, Qp, Gp, maskp, carry0)
        if isinstance(seed, _CarryHandle):
            seed.release()

        # the controller state stays device-resident for the store; the
        # result itself resolves through ONE counted blocking fetch
        host = self._sync_fetch(outs)
        base = collect_result(host, order=order, crop=(n, m))
        res = ServiceMatchResult(**{f.name: getattr(base, f.name)
                                    for f in dataclasses.fields(MatchResult)})
        if self.warm_start:
            stored = self._pool.put((outs["S_star"], outs["f_star"],
                                     outs["S_bar"])) \
                if self.mesh is None else res.carry
            self._store_carry(req, warm_key, stored, similar=res.found)
        self.stats.epochs_run += res.epochs_run
        self._note_prune(1, res.prune_sweeps)
        if res.found:
            self.stats.found += 1
        if res.carry_verified:
            # the in-kernel fast path IS Tier 0 for a single call
            self.stats.carry_fastpath_hits += 1
            self.stats.tier0.hits += 1
            res.tier = 0
        else:
            self.stats.tier2.launches += 1
            self.stats.tier2_fetch_bytes += _tree_nbytes(host)
            self.stats.epoch_fused_launches += 1
            self.stats.epoch_finish_launches += 1
            self.stats.epoch_finish_problems += 1
            self.stats.tier2.checked += 1
            if res.found:
                self.stats.tier2.hits += 1
            res.tier = 2
        res.bucket = bucket
        res.compile_cache_hit = compile_hit
        res.warm_hit = warm_hit
        res.latency_s = time.perf_counter() - t0
        return res

    # -- request coalescing ------------------------------------------------

    def submit(self, query: Graph, target: Graph,
               key: Optional[jax.Array] = None, workload_key=None,
               engine_sig: Optional[bytes] = None,
               rid: Optional[int] = None,
               drain: Optional[int] = None) -> int:
        """Queue a problem for the next ``drain``; returns its ticket
        index into the results list ``drain`` will return. ``rid`` and
        ``drain`` (the front end's request id and drain number) tag its
        spans."""
        self._pending.append(self._prepare(query, target, key, workload_key,
                                           engine_sig, rid, drain))
        return len(self._pending) - 1

    @property
    def pending(self) -> int:
        """Number of submitted problems waiting for the next drain."""
        return len(self._pending)

    def drain(self) -> List[ServiceMatchResult]:
        """Flush the pending queue through the tiered pipeline.

        Same-bucket requests form one pipeline group: Tier 0 revalidates
        every stored carry in one cheap launch, Tier 1 rebases similar
        carries for the misses, and only the residual requests launch the
        Tier-2 swarm (chunked to batch classes). Results come back in
        submission order; each request's ``latency_s`` is the wall time
        of the launches that actually served it, so an easy request no
        longer pays a hard neighbour's epochs.

        With ``pipelined=True`` (the default) each tier dispatches its
        launches for EVERY bucket group before anything blocks: the host
        builds and enqueues group B's batch while the device still runs
        group A's, and each stage resolves through one batched fetch —
        an all-warm drain costs exactly one blocking host sync
        (``stats.host_syncs_per_drain``). ``pipelined=False`` restores
        the legacy serial walk: carries staged through host numpy (one
        implicit sync per device-resident carry part) and one blocking
        fetch per launch.
        """
        pending, self._pending = self._pending, []
        if not pending:
            return []
        self.stats.drains += 1
        results: List[Optional[ServiceMatchResult]] = [None] * len(pending)
        groups: "OrderedDict[Tuple[int, int], List[int]]" = OrderedDict()
        for i, req in enumerate(pending):
            groups.setdefault(req.bucket, []).append(i)
        if self._tiers_active() and self.pipelined:
            self._drain_pipelined(pending, groups, results)
            return results  # type: ignore[return-value]
        max_chunk = self.batch_classes[-1]
        for bucket, idxs in groups.items():
            reqs = [pending[i] for i in idxs]
            if self._tiers_active():
                self._run_pipeline(bucket, reqs, idxs, results)
            else:
                for pos in range(0, len(idxs), max_chunk):
                    chunk = idxs[pos:pos + max_chunk]
                    self._launch_batch_legacy(
                        bucket, [pending[i] for i in chunk], chunk, results)
        return results  # type: ignore[return-value]

    def match_many(self, problems: Sequence[Tuple[Graph, Graph]],
                   keys: Optional[Sequence[jax.Array]] = None,
                   workload_keys: Optional[Sequence] = None,
                   engine_sigs: Optional[Sequence[Optional[bytes]]] = None
                   ) -> List[ServiceMatchResult]:
        """Convenience: submit a burst of (query, target) problems and
        drain them through the tiered pipeline."""
        for i, (q, g) in enumerate(problems):
            self.submit(q, g,
                        key=None if keys is None else keys[i],
                        workload_key=(None if workload_keys is None
                                      else workload_keys[i]),
                        engine_sig=(None if engine_sigs is None
                                    else engine_sigs[i]))
        return self.drain()

    # -- the tiered pipeline ----------------------------------------------

    def _intake(self, reqs: List[_PendingRequest], tickets: List[int]
                ) -> List[_PipelineItem]:
        """Shared per-request intake for both drain paths: call/budget
        accounting, exact-carry lookup, group coalescing stats."""
        t_start = time.perf_counter()
        items: List[_PipelineItem] = []
        for req, ticket in zip(reqs, tickets):
            self.stats.calls += 1
            self.stats.epochs_budgeted += self.cfg.epochs
            wk = self._warm_key(req)
            carry, hit = self._get_carry(wk)
            items.append(_PipelineItem(req=req, ticket=ticket, warm_key=wk,
                                       carry=carry, warm_hit=hit,
                                       t0=t_start))
        if len(items) > 1:
            # the group shares ONE pipeline decision, whichever tier ends
            # up serving each member
            self.stats.coalesced_requests += len(items)
        return items

    def _run_pipeline(self, bucket, reqs: List[_PendingRequest],
                      tickets: List[int], results: List) -> None:
        """Revalidate → similarity-rebase → swarm for one bucket group."""
        items = self._intake(reqs, tickets)
        max_chunk = self.batch_classes[-1]

        # ---- Tier 0: batched revalidation of every stored carry ----
        residual: List[_PipelineItem] = [it for it in items
                                         if it.carry is None]
        cand = [it for it in items if it.carry is not None]
        for pos in range(0, len(cand), max_chunk):
            chunk = cand[pos:pos + max_chunk]
            residual.extend(self._launch_revalidate(
                bucket, chunk, [it.carry for it in chunk], tier=0))

        # ---- Tier 1: rebase the nearest similar carry for the misses ----
        if self.similarity and residual:
            t1_items, t1_carries = [], []
            for it in residual:
                nb = self._lookup_neighbor(it)
                if nb is not None:
                    t1_items.append(it)
                    t1_carries.append(nb)
            for pos in range(0, len(t1_items), max_chunk):
                self._launch_revalidate(
                    bucket, t1_items[pos:pos + max_chunk],
                    t1_carries[pos:pos + max_chunk], tier=1)

        # ---- Tier 2: swarm sized to the residual (hard) subset ----
        residual = [it for it in items if it.result is None]
        for pos in range(0, len(residual), max_chunk):
            self._launch_swarm(bucket, residual[pos:pos + max_chunk])

        for it in items:
            it.result.latency_s = it.latency_s
            results[it.ticket] = it.result

    def _drain_pipelined(self, pending: List[_PendingRequest],
                         groups: "OrderedDict[Tuple[int, int], List[int]]",
                         results: List) -> None:
        """Async-dispatch drain: every bucket group's launches for one
        tier go out before ANY of them blocks, then the whole stage
        resolves through a single batched fetch (``_apply_all``).

        Host-side tier decisions for later groups (padding, carry
        assembly, store probes) overlap device execution of earlier
        groups' launches, and the per-stage sync count is 1 instead of
        one per launch. Results and stored carries are bitwise identical
        to the serial walk: store keys embed the bucket, so groups never
        interact, and within a group the tier order and miss order are
        preserved exactly."""
        max_chunk = self.batch_classes[-1]
        # ---- Tier 0: dispatch every group's revalidation launches ----
        recs: List[_LaunchRecord] = []
        state = []                 # (bucket, items, residual) per group
        for bucket, idxs in groups.items():
            items = self._intake([pending[i] for i in idxs], idxs)
            residual = [it for it in items if it.carry is None]
            cand = [it for it in items if it.carry is not None]
            for pos in range(0, len(cand), max_chunk):
                chunk = cand[pos:pos + max_chunk]
                recs.append(self._dispatch_revalidate(
                    bucket, chunk, [it.carry for it in chunk], tier=0,
                    miss_sink=residual))
            state.append((bucket, items, residual))
        self._apply_all(recs)

        # ---- Tier 1: rebase lookups + dispatches across all groups ----
        recs = []
        for bucket, items, residual in state:
            if not (self.similarity and residual):
                continue
            t1_items, t1_carries = [], []
            for it in residual:
                nb = self._lookup_neighbor(it)
                if nb is not None:
                    t1_items.append(it)
                    t1_carries.append(nb)
            for pos in range(0, len(t1_items), max_chunk):
                recs.append(self._dispatch_revalidate(
                    bucket, t1_items[pos:pos + max_chunk],
                    t1_carries[pos:pos + max_chunk], tier=1,
                    miss_sink=[]))
        self._apply_all(recs)

        # ---- Tier 2: swarm the residual of every group ----
        recs = []
        for bucket, items, _ in state:
            residual = [it for it in items if it.result is None]
            for pos in range(0, len(residual), max_chunk):
                recs.append(self._dispatch_swarm(
                    bucket, residual[pos:pos + max_chunk]))
        self._apply_all(recs)

        for _, items, _ in state:
            for it in items:
                it.result.latency_s = it.latency_s
                results[it.ticket] = it.result

    def _apply_all(self, recs: List[_LaunchRecord]) -> None:
        """Resolve one pipeline stage: ONE blocking fetch covering every
        dispatched launch's outputs, then the per-launch applies in
        dispatch order (which preserves the serial walk's store/miss
        ordering)."""
        if not recs:
            return
        hosts = self._sync_fetch([self._fetch_tree(rec) for rec in recs],
                                 recs[0].drain)
        for rec, host in zip(recs, hosts):
            if rec.kind == "reval":
                self._apply_revalidate(rec, host)
            else:
                self._apply_swarm(rec, host)

    def _lookup_neighbor(self, item: _PipelineItem) -> Optional[tuple]:
        """Similarity-store probe for one Tier-0 miss; returns the carry
        of the nearest stored platform state, or None."""
        req = item.req
        if req.engine_sig is None:
            return None
        self.stats.sim_lookups += 1
        nb = self._carries.nearest(
            req.qdigest, req.bucket, req.engine_sig,
            # the exact carry already failed revalidation — don't retry it
            exclude_sig=req.engine_sig if item.carry is not None else None)
        if nb is None:
            return None
        self.stats.sim_neighbor_hits += 1
        return nb[1]

    def _launch_revalidate(self, bucket, items: List[_PipelineItem],
                           carries: List[tuple], tier: int
                           ) -> List[_PipelineItem]:
        """One *serial* Tier-0/1 launch: dispatch, then a blocking fetch
        of just this launch's outputs (one sync per launch — the arm
        ``bench_pipeline`` measures the pipelined drain against).

        Hits get their result attached (0 epochs, revalidation cost);
        misses are returned for the next tier. Tier-1 misses keep the
        rebased carry (f* reset to -inf) as their Tier-2 swarm seed."""
        misses: List[_PipelineItem] = []
        rec = self._dispatch_revalidate(bucket, items, carries, tier,
                                        miss_sink=misses)
        self._apply_revalidate(rec, self._sync_fetch(self._fetch_tree(rec),
                                                     rec.drain))
        return misses

    def _dispatch_revalidate(self, bucket, items: List[_PipelineItem],
                             carries: List[tuple], tier: int,
                             miss_sink: List) -> _LaunchRecord:
        """Enqueue one Tier-0/1 revalidation launch (no host sync): pad
        the batch, stack the carries device-side, dispatch. The returned
        record resolves via ``_apply_revalidate`` once its outputs are
        fetched."""
        B = len(items)
        bclass = self._batch_class(B)
        tstats = self.stats.tier0 if tier == 0 else self.stats.tier1
        drain = items[0].req.drain

        with _span("dispatch", tier=tier, B=B, bclass=bclass, drain=drain):
            hits_before = self.stats.compile_cache_hits
            fn = self._executable_reval(bucket, bclass)
            compile_hit = self.stats.compile_cache_hits > hits_before

            reqs = [it.req for it in items]
            stored = list(carries)
            padded, carries = list(reqs), list(carries)
            if bclass > B:
                pad_req, pad_carry = self._pad_slot(bucket, reqs[0],
                                                    carries[0])
                padded += [pad_req] * (bclass - B)
                carries += [pad_carry] * (bclass - B)
            Qb = np.stack([r.Qp for r in padded])
            Gb = np.stack([r.Gp for r in padded])
            maskb = np.stack([r.maskp for r in padded])
            if self._pooled:
                maskb = jnp.asarray(maskb)
                carry0 = self._pool.assemble(carries, maskb)
            else:
                carry0 = self._stack_carries(carries)
            if self.mesh is None and self._donate_argnums("reval"):
                self.stats.donated_launches += 1

            outs = fn(Qb, Gb, maskb, carry0)
            tstats.launches += 1
            tstats.checked += B
            return _LaunchRecord(kind="reval", bucket=bucket, items=items,
                                 tier=tier, B=B, bclass=bclass,
                                 compile_hit=compile_hit, outs=outs,
                                 carries=stored, miss_sink=miss_sink,
                                 drain=drain)

    def _apply_revalidate(self, rec: _LaunchRecord, host: dict) -> None:
        """Consume one fetched revalidation launch: attach hit results,
        append misses to the record's sink (with their Tier-2 seeds),
        refresh stores. All array reads come from ``host`` or stay on
        device — this path never blocks."""
        with _span("apply", tier=rec.tier, drain=rec.drain):
            tier, B, items, carries = rec.tier, rec.B, rec.items, rec.carries
            tstats = self.stats.tier0 if tier == 0 else self.stats.tier1
            # Tier 0 re-validates this problem's own carry (carried-f* gate);
            # Tier 1 additionally requires the rebased projection to clear the
            # fitness bound on THIS problem (stored f* isn't transferable)
            ok = np.asarray(host["ok" if tier == 0 else "ok_rebase"])
            maps = np.asarray(host["mapping"])
            # leaves outside this tier's _fetch_tree subset stay on device
            fits = host.get("fitness")
            S_rb = host.get("S_star")
            S_bar_rb = host.get("S_bar")
            f_carry = host.get("f_carry")
            sweeps = np.asarray(host["prune_sweeps"]).reshape(-1)
            self._note_prune(B, int(sweeps[:B].sum()))
            done = time.perf_counter()
            # Tier 1 stores every hit's rebased carry, and keeps each
            # cold miss's rebased carry (f* reset to -inf) as its Tier-2
            # seed; a miss with a failed exact carry swarms from that
            # instead. Single-device services write them all to pool rows
            # in one program; a seed row is held by its item until its
            # swarm launch is dispatched
            written = {}
            if tier == 1:
                hit = [j for j in range(B) if ok[j]]
                seed = [j for j, it in enumerate(items)
                        if not ok[j] and it.carry is None]
                if self.mesh is None and (hit or seed):
                    written = dict(zip(hit + seed, self._pool.write_back(
                        rec.outs["S_star"], rec.outs["fitness"],
                        rec.outs["S_bar"], hit, seed)))

            for j, it in enumerate(items):
                it.latency_s = done - it.t0
                if not ok[j]:
                    if tier == 1 and it.carry is None:
                        if j in written:
                            it.seed = written[j]
                            it.seed.retain()
                        else:
                            it.seed = (S_rb[j], np.float32(-np.inf),
                                       S_bar_rb[j])
                    rec.miss_sink.append(it)
                    continue
                tstats.hits += 1
                self.stats.carry_fastpath_hits += 1
                self.stats.found += 1
                if tier == 0:
                    # the stored carry revalidated: it stays in the store
                    # untouched; its f* comes from the output echo, not a
                    # per-item device read, and the result's carry is a lazy
                    # view — no pool slicing unless the caller looks at it
                    carry = (_LazyCarry(carries[j])
                             if isinstance(carries[j], _CarryHandle)
                             else self._carry_tuple(carries[j]))
                    f_res = float(f_carry[j])
                else:
                    carry = (S_rb[j], fits[j], S_bar_rb[j])
                    f_res = float(fits[j])
                    self._store_carry(it.req, it.warm_key,
                                      written.get(j, carry), similar=True)
                it.result = self._revalidated_result(
                    it, maps[j], f_res, carry, tier=tier, batch=B,
                    compile_hit=rec.compile_hit, prune_sweeps=int(sweeps[j]))

    def _revalidated_result(self, item: _PipelineItem, M_c: np.ndarray,
                            f_res: float, carry, *, tier: int, batch: int,
                            compile_hit: bool, prune_sweeps: int = 0
                            ) -> ServiceMatchResult:
        """Host-side result for a request served by revalidation alone —
        the 0-epoch equivalent of what ``collect_result`` produces when
        the in-kernel fast path skipped every epoch."""
        req, cfg = item.req, self.cfg
        n, m = req.crop
        M = np.asarray(M_c)[:n, :m]
        unperm = np.empty_like(M)
        unperm[req.order, :] = M
        return ServiceMatchResult(
            mapping=unperm,
            feasible_count=0,
            f_star=f_res,
            f_star_trace=np.full((cfg.epochs, cfg.inner_steps), f_res,
                                 np.float32),
            all_mappings=np.zeros((0, n, m), np.uint8),
            all_feasible=np.zeros((0,), bool),
            all_fitness=np.zeros((0,), np.float32),
            carry=carry, epochs_run=0, carry_verified=True,
            prune_sweeps=prune_sweeps,
            bucket=req.bucket, compile_cache_hit=compile_hit,
            warm_hit=item.warm_hit, batch_size=batch,
            coalesced=batch > 1, tier=tier)

    # -- batch launches ----------------------------------------------------

    def _pad_slot(self, bucket, like: _PendingRequest, like_carry
                  ) -> Tuple[_PendingRequest, tuple]:
        """Pad filler for a batch launch: a trivial problem whose carry
        re-validates in epoch 0, so ``scan_epochs_batch`` freezes the pad
        slots immediately instead of re-burning a real problem's epoch
        budget (the old behaviour replicated problem 0 verbatim). Falls
        back to that replication (slot 0's problem AND carry, so the pad
        mirrors its trajectory exactly) for the degenerate n_pad > m_pad
        buckets where no injective trivial mask exists."""
        n_pad, m_pad = bucket
        if m_pad < n_pad:
            return like, like_carry
        Qp = np.zeros((n_pad, n_pad), dtype=like.Qp.dtype)
        Gp = np.zeros((m_pad, m_pad), dtype=like.Gp.dtype)
        maskp = np.zeros((n_pad, m_pad), dtype=like.maskp.dtype)
        idx = np.arange(n_pad)
        maskp[idx, idx] = 1
        S_id = np.zeros((n_pad, m_pad), np.float32)
        S_id[idx, idx] = 1.0
        # f* = +inf clears ANY early_exit_fitness bound, so the pad slot
        # is pre-finished regardless of the configured threshold
        if self.mesh is None:
            carry = self._pad_handles.get(bucket)
            if carry is None:
                carry = self._pool.put((S_id, np.float32(np.inf), S_id))
                carry.retain()     # pinned: pads recur on every drain
                self._pad_handles[bucket] = carry
        else:
            carry = (S_id, np.float32(np.inf), S_id.copy())
        req = _PendingRequest(key=like.key, workload_key=None,
                              order=np.arange(n_pad),
                              crop=(n_pad, m_pad), bucket=bucket,
                              Qp=Qp, Gp=Gp, maskp=maskp)
        return req, carry

    def _launch_swarm(self, bucket, items: List[_PipelineItem]) -> None:
        """One *serial* Tier-2 swarm launch over the pipeline's residual
        items: dispatch, then a blocking fetch of just this launch's
        outputs (the one-sync-per-launch baseline arm)."""
        rec = self._dispatch_swarm(bucket, items)
        self._apply_swarm(rec, self._sync_fetch(self._fetch_tree(rec),
                                                rec.drain))

    def _dispatch_swarm(self, bucket, items: List[_PipelineItem]
                        ) -> _LaunchRecord:
        """Enqueue one Tier-2 swarm launch (no host sync) over items
        whose carries are already resolved: failed exact carry, rebased
        neighbour seed, or the cold prior."""
        B = len(items)
        bclass = self._batch_class(B)
        drain = items[0].req.drain

        with _span("dispatch", tier=2, B=B, bclass=bclass, drain=drain):
            hits_before = self.stats.compile_cache_hits
            fn = self._executable_batch(bucket, bclass)
            compile_hit = self.stats.compile_cache_hits > hits_before

            reqs = [it.req for it in items]
            # a slot's carry: its failed exact carry, its rebased seed,
            # or None for the cold prior
            carries = [it.carry if it.carry is not None else it.seed
                       for it in items]

            pad = bclass - B
            padded = list(reqs)
            if pad:
                pad_req, pad_carry = self._pad_slot(bucket, reqs[0],
                                                    carries[0])
                padded += [pad_req] * pad
                carries = carries + [pad_carry] * pad
                if pad_req is not reqs[0] and self.cfg.early_exit \
                        and self.cfg.carry_fastpath:
                    self.stats.pad_slots_frozen += pad
            keys = [r.key for r in padded]
            if self.mesh is None and not all(isinstance(k, np.ndarray)
                                             for k in keys):
                # device keys stack device-side: np.asarray would be a
                # hidden sync per key
                keysb = jnp.stack(keys)
            else:
                keysb = np.stack([np.asarray(k) for k in keys])
            Qb = np.stack([r.Qp for r in padded])
            Gb = np.stack([r.Gp for r in padded])
            maskb = np.stack([r.maskp for r in padded])
            if self._pooled:
                maskb = jnp.asarray(maskb)
                carry0 = self._pool.assemble(carries, maskb)
            else:
                carry0 = self._stack_carries([
                    c if c is not None
                    else pso.default_carry(jnp.asarray(r.maskp))
                    for c, r in zip(carries, padded)])
            if self.mesh is None and self._donate_argnums("batch"):
                self.stats.donated_launches += 1

            outs = fn(keysb, Qb, Gb, maskb, carry0)
            for it in items:
                if isinstance(it.seed, _CarryHandle):
                    # the launch has read the seed row: give it back
                    it.seed.release()
                    it.seed = None
            self.stats.batch_launches += 1
            self.stats.batch_problems += B
            self.stats.batch_slots += bclass
            self.stats.tier2.launches += 1
            self.stats.epoch_fused_launches += 1
            self.stats.epoch_finish_launches += 1
            self.stats.epoch_finish_problems += B
            self.stats.tier2.checked += B
            return _LaunchRecord(kind="swarm", bucket=bucket, items=items,
                                 tier=2, B=B, bclass=bclass,
                                 compile_hit=compile_hit, outs=outs,
                                 padded=padded, drain=drain)

    def _apply_swarm(self, rec: _LaunchRecord, host: dict) -> None:
        """Consume one fetched swarm launch: build per-item results from
        the host outputs, store the still-on-device controller state for
        future warm starts."""
        with _span("apply", tier=2, drain=rec.drain):
            items, B, padded = rec.items, rec.B, rec.padded
            self.stats.tier2_fetch_bytes += _tree_nbytes(host)
            orders = [r.order for r in padded]
            crops = [r.crop for r in padded]
            if self.mesh is None:
                # the program selected each served mapping; every item's
                # controller state goes to the pool, all B rows in one
                # program straight from the device outputs, and its
                # result carries a lazy view of its row
                batch_results = collect_served_results(host, B, orders,
                                                       crops)
                stored = self._pool.write_back(
                    rec.outs["S_star"], rec.outs["f_star"],
                    rec.outs["S_bar"], range(B))
                for r, h in zip(batch_results, stored):
                    r.carry = _LazyCarry(h)
            else:
                batch_results = collect_batch_results(host, rec.bclass,
                                                      orders, crops)
                stored = [r.carry for r in batch_results[:B]]
            done = time.perf_counter()

            for j, it in enumerate(items):
                base = batch_results[j]
                res = ServiceMatchResult(
                    **{f.name: getattr(base, f.name)
                       for f in dataclasses.fields(MatchResult)})
                if self.warm_start:
                    self._store_carry(it.req, it.warm_key, stored[j],
                                      similar=res.found)
                self.stats.epochs_run += res.epochs_run
                self._note_prune(1, res.prune_sweeps)
                if res.found:
                    self.stats.found += 1
                    self.stats.tier2.hits += 1
                if res.carry_verified:
                    self.stats.carry_fastpath_hits += 1
                res.bucket = rec.bucket
                res.compile_cache_hit = rec.compile_hit
                res.warm_hit = it.warm_hit
                res.batch_size = B
                res.coalesced = B > 1
                res.tier = 2
                # end-to-end drain latency: a Tier-2 request also waited out
                # every pipeline launch that preceded this one
                it.latency_s = done - it.t0
                it.result = res

    def _launch_batch_legacy(self, bucket, reqs: List[_PendingRequest],
                             tickets: List[int], results: List) -> None:
        """The untiered (PR-2) drain path: every request goes straight to
        one uniform swarm launch. Kept as the ``tiered=False`` baseline —
        `benchmarks/bench_tiers.py` measures the pipeline against it."""
        items = self._intake(reqs, tickets)
        self._launch_swarm(bucket, items)
        for it in items:
            it.result.latency_s = it.latency_s
            results[it.ticket] = it.result

    # -- reporting ---------------------------------------------------------

    def stats_dict(self) -> Dict[str, float]:
        """Flat ``{counter: value}`` export of :class:`ServiceStats`
        plus derived rates and per-tier breakdowns — the payload
        ``SimResult.matcher_stats`` surfaces (see the README stats
        glossary for per-key meanings)."""
        s = self.stats
        out = {
            "calls": s.calls,
            "compile_cache_hits": s.compile_cache_hits,
            "compile_cache_misses": s.compile_cache_misses,
            "compile_hit_rate": s.compile_hit_rate,
            "warm_hits": s.warm_hits,
            "warm_misses": s.warm_misses,
            "warm_hit_rate": s.warm_hit_rate,
            "epochs_run": s.epochs_run,
            "epochs_budgeted": s.epochs_budgeted,
            "epochs_saved": s.epochs_saved,
            "epoch_fused_launches": s.epoch_fused_launches,
            "epoch_finish_launches": s.epoch_finish_launches,
            "epoch_finish_problems": s.epoch_finish_problems,
            "epoch_backend": kernel_backend.resolve_backend_name(
                self.cfg.backend),
            "found": s.found,
            "batch_launches": s.batch_launches,
            "coalesced_requests": s.coalesced_requests,
            "batch_problems": s.batch_problems,
            "batch_slots": s.batch_slots,
            "batch_occupancy": s.batch_occupancy,
            "carry_fastpath_hits": s.carry_fastpath_hits,
            "revalidated_rate": s.revalidated_rate,
            "pad_slots_frozen": s.pad_slots_frozen,
            "prune_problems": s.prune_problems,
            "prune_sweeps": s.prune_sweeps,
            "avg_prune_sweeps": s.avg_prune_sweeps,
            "sim_lookups": s.sim_lookups,
            "sim_neighbor_hits": s.sim_neighbor_hits,
            "sim_evictions": s.sim_evictions,
            "sim_entries": self._carries.sim_entries,
            "jit_traces": s.jit_traces,
            "aot_cache_hits": s.aot_cache_hits,
            "aot_cache_misses": s.aot_cache_misses,
            "aot_exports": s.aot_exports,
            "aot_export_failures": s.aot_export_failures,
            "aot_call_fallbacks": s.aot_call_fallbacks,
            "snapshot_saves": s.snapshot_saves,
            "snapshot_restores": s.snapshot_restores,
            "snapshot_stale_skipped": s.snapshot_stale_skipped,
            "snapshot_skipped_keys": s.snapshot_skipped_keys,
            "restored_carries": s.restored_carries,
            "restored_sim_entries": s.restored_sim_entries,
            "fe_submitted": s.fe_submitted,
            "fe_admitted": s.fe_admitted,
            "fe_shed": s.fe_shed,
            "fe_forced_drains": s.fe_forced_drains,
            "fe_drains": s.fe_drains,
            "fe_drain_deadline": s.fe_drain_deadline,
            "fe_drain_batch_full": s.fe_drain_batch_full,
            "fe_drain_flush": s.fe_drain_flush,
            "fe_queue_peak": s.fe_queue_peak,
            "fe_wait_s": s.fe_wait_s,
            "drains": s.drains,
            "host_syncs": s.host_syncs,
            "host_syncs_per_drain": s.host_syncs_per_drain,
            "host_bytes_transferred": s.host_bytes_transferred,
            "host_sync_wall_s": s.host_sync_wall_s,
            "tier2_fetch_bytes": s.tier2_fetch_bytes,
            "donated_launches": s.donated_launches,
            "pool_puts": self._pool.puts,
            "pool_writes": self._pool.writes,
            "pool_gathers": self._pool.gathers,
            "pool_live_rows": self._pool.live_rows,
        }
        for name in ("tier0", "tier1", "tier2"):
            t: TierStats = getattr(s, name)
            out[f"{name}_launches"] = t.launches
            out[f"{name}_checked"] = t.checked
            out[f"{name}_hits"] = t.hits
            out[f"{name}_hit_rate"] = t.hit_rate
        return out


@dataclasses.dataclass
class _QueuedRequest:
    rid: int
    query: Graph
    target: Graph
    deadline: float
    enqueued_at: float
    key: Optional[jax.Array] = None
    workload_key: object = None
    engine_sig: Optional[bytes] = None


class AsyncServiceFrontEnd:
    """Admission-controlled arrival queue in front of a MatcherService.

    ``MatcherService.submit``/``drain`` are caller-driven: whoever
    submits must also decide when to flush, so under sustained load the
    queue either grows without bound or gets drained one request at a
    time. This front end owns that decision. Requests enter a bounded
    queue (``max_depth``); when it is full the ``policy`` either
    **sheds** the new request (recorded, result ``None``) or **blocks**
    it by forcing a drain round to make room first. A queued batch is
    drained through the service's tiered pipeline when either

      * the queue can fill the service's largest batch class
        (``batch_classes[-1]`` requests queued) — launch-shaped, or
      * the *oldest* queued request's slack ``deadline - now`` falls to
        ``slack_threshold_s`` — deadline-shaped (checked at submit time
        and by ``poll``), or
      * the caller explicitly ``flush``\\ es.

    Every trigger reason, shed, forced drain, queue peak, and cumulative
    queue wait flows into the service's ``ServiceStats`` (``fe_*`` keys
    of ``stats_dict()``), so ``SimResult.matcher_stats`` →
    ``metrics.frontend_stats`` report it per run. Each drain round runs
    under an ``immsched.drain`` profiler span (see the module docstring).

    Time is an explicit ``now`` parameter everywhere (falling back to
    ``clock()``), so the front end drops into the event-driven simulator
    — which advances virtual time — as readily as onto a wall clock.
    """

    def __init__(self, service: MatcherService, *, max_depth: int = 64,
                 policy: str = "shed", slack_threshold_s: float = 0.0,
                 clock=time.perf_counter):
        assert policy in ("shed", "block"), policy
        assert max_depth >= 1
        self.service = service
        self.max_depth = int(max_depth)
        self.policy = policy
        self.slack_threshold_s = float(slack_threshold_s)
        self._clock = clock
        self._queue: List[_QueuedRequest] = []
        self._results: Dict[int, Optional[ServiceMatchResult]] = {}
        self._next_rid = 0

    # -- observables ---------------------------------------------------

    @property
    def depth(self) -> int:
        """Requests currently queued (admitted, not yet drained)."""
        return len(self._queue)

    def next_deadline_check(self) -> float:
        """Earliest instant the deadline trigger could fire (the oldest
        queued deadline minus the slack threshold); +inf when idle. An
        event-driven host schedules its next ``poll`` here."""
        if not self._queue:
            return float("inf")
        return min(q.deadline for q in self._queue) - self.slack_threshold_s

    # -- request path --------------------------------------------------

    def submit(self, query: Graph, target: Graph, *,
               deadline: float = float("inf"),
               now: Optional[float] = None,
               key: Optional[jax.Array] = None, workload_key=None,
               engine_sig: Optional[bytes] = None) -> int:
        """Offer a request; returns a request id for ``take_result``.

        A shed request (queue full under the shed policy) still gets an
        id — its result is recorded as ``None`` immediately.
        """
        now = self._clock() if now is None else now
        stats = self.service.stats
        rid = self._next_rid
        self._next_rid += 1
        stats.fe_submitted += 1
        if len(self._queue) >= self.max_depth:
            if self.policy == "shed":
                stats.fe_shed += 1
                self._results[rid] = None
                return rid
            stats.fe_forced_drains += 1
            self._drain(now, "batch_full")
        self._queue.append(_QueuedRequest(
            rid=rid, query=query, target=target, deadline=float(deadline),
            enqueued_at=now, key=key, workload_key=workload_key,
            engine_sig=engine_sig))
        stats.fe_admitted += 1
        stats.fe_queue_peak = max(stats.fe_queue_peak, len(self._queue))
        self._check_triggers(now)
        return rid

    def poll(self, now: Optional[float] = None) -> int:
        """Fire any due drain trigger; returns requests drained (0 if
        none due). Hosts call this when time passes without submits —
        e.g. at ``next_deadline_check()``."""
        now = self._clock() if now is None else now
        return self._check_triggers(now)

    def flush(self, now: Optional[float] = None) -> int:
        """Drain everything queued regardless of triggers."""
        now = self._clock() if now is None else now
        return self._drain(now, "flush")

    def take_result(self, rid: int) -> Optional[ServiceMatchResult]:
        """Pop the result for ``rid``: a ``ServiceMatchResult``, or
        ``None`` if the request was shed. Raises ``KeyError`` while the
        request is still queued (not drained yet)."""
        return self._results.pop(rid)

    # -- internals -----------------------------------------------------

    def _check_triggers(self, now: float) -> int:
        if not self._queue:
            return 0
        if len(self._queue) >= self.service.batch_classes[-1]:
            return self._drain(now, "batch_full")
        oldest_slack = min(q.deadline for q in self._queue) - now
        if oldest_slack <= self.slack_threshold_s:
            return self._drain(now, "deadline")
        return 0

    def _drain(self, now: float, reason: str) -> int:
        if not self._queue:
            return 0
        stats = self.service.stats
        stats.fe_drains += 1
        setattr(stats, f"fe_drain_{reason}",
                getattr(stats, f"fe_drain_{reason}") + 1)
        batch, self._queue = self._queue, []
        svc, n = self.service, stats.fe_drains
        with _span("drain", drain=n, reason=reason, requests=len(batch)):
            tickets = [svc.submit(q.query, q.target, key=q.key,
                                  workload_key=q.workload_key,
                                  engine_sig=q.engine_sig, rid=q.rid,
                                  drain=n)
                       for q in batch]
            results = svc.drain()
            for q, ticket in zip(batch, tickets):
                self._results[q.rid] = results[ticket]
                stats.fe_wait_s += max(now - q.enqueued_at, 0.0)
        return len(batch)
