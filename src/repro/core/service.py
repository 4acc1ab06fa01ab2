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
    each call is remembered in a two-level :class:`CarryStore` (the carry
    path lives in :mod:`repro.core.carries`): an *exact*
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

from repro.checkpoint.manager import CheckpointManager
from repro.core import persist, pso
from repro.core.carries import (CarryPath, CarryStore, carry_tuple,
                                release, result_view, retain)
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


def _chunks(seq: List, size: int) -> List[List]:
    """``seq`` cut into consecutive launches of at most ``size``."""
    return [seq[i:i + size] for i in range(0, len(seq), size)]


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


class MatcherService:
    """Warm-start online wrapper around Algorithm 1.

    Single-device by default; pass ``mesh`` + ``axis_names`` to run each
    bucket's executable as the collective-fused distributed matcher.
    ``tiered=False`` turns Tier 0/1 off, so the tier walk sends every
    request to a uniform swarm launch per batch (the PR-2 baseline);
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
        # pipelined=False: the tier walk resolves each launch as it is
        # dispatched and stages carries through host numpy — the serial
        # arm bench_pipeline measures the pipeline against
        self.pipelined = bool(pipelined)
        if donate_buffers is None:
            donate_buffers = pallas_compat.donation_supported()
        self.donate_buffers = bool(donate_buffers)
        self.stats = ServiceStats()
        self._carries = CarryStore(warm_capacity, sim_capacity, self.stats,
                                   sim_index=sim_index)
        self._path = CarryPath(self.stats, single_device=mesh is None,
                               pipelined=self.pipelined)
        self._pool = self._path.pool
        self._pad_handles = self._path.pads
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

    # a stored carry as its (S*, f*, S̄) tuple, whatever its stored form
    _carry_tuple = staticmethod(carry_tuple)

    def _get_carry(self, warm_key):
        if not self.warm_start:
            self.stats.warm_misses += 1
            return None, False
        return self._carries.get(warm_key)

    def _store_carry(self, req: _PendingRequest, warm_key, stored,
                     similar: bool) -> None:
        """Store a fresh carry (in its ``CarryPath`` stored form) under
        the exact key, and — when the call produced a served decision
        (``similar``) on a known platform state — under the similarity
        key too, so future drifted states can rebase it."""
        if not self.warm_start:
            return
        self._carries.put(warm_key, stored)
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
        arrays: Dict[str, np.ndarray] = {}
        keys: Dict[str, List] = {}
        for store, items in zip(("exact", "sim"),
                                self._carries.export_state()):
            keys[store], carries = [], []
            for k, c in items:
                try:
                    keys[store].append(persist.encode_key(k))
                except TypeError:
                    self.stats.snapshot_skipped_keys += 1
                    continue
                # device-pool handles materialize to lazy device slices
                # here; the ONE blocking transfer is inside carry_leaves
                carries.append(carry_tuple(c))
            arrays.update(persist.carry_leaves(store, carries))
        # flat-dict checkpoints must be non-empty for restore_flat to see
        # a committed structure even when no carries are stored yet
        arrays["snapshot.marker"] = np.zeros((), np.int8)
        extras = {
            "format_version": persist.SNAPSHOT_VERSION,
            "config_digest": self.config_digest,
            "exact_keys": keys["exact"],
            "sim_keys": keys["sim"],
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
        loaded = []
        for store in ("exact", "sim"):
            keys = [persist.decode_key(k) for k in extras[f"{store}_keys"]]
            loaded.append(list(zip(keys, persist.carries_from_leaves(
                store, arrays, len(keys)))))
        # restored carries go back to the service's stored form (pool rows
        # on a single device, uploaded once); rows free themselves as
        # store replay/evictions release the handles
        n_exact, n_sim = self._carries.import_state(
            *[[(k, self._path.keep(c)) for k, c in items]
              for items in loaded])
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
        (``dtype``/``shape``/bytes — via ``carries.carry_tuple``, so
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
            return ([(k, carry_tuple(c)) for k, c in exact],
                    [(k, carry_tuple(c)) for k, c in sim])

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
                self._apply_all([self._dispatch_revalidate(
                    bucket, [item], [nb], tier=1, miss_sink=[])])
                if item.result is not None:
                    res = item.result
                    res.latency_s = time.perf_counter() - t0
                    return res
                seed = item.seed

        hits_before = self.stats.compile_cache_hits
        fn = self._executable(bucket)
        compile_hit = self.stats.compile_cache_hits > hits_before

        if carry0 is None:
            carry0 = carry_tuple(seed) if seed is not None \
                else pso.default_carry(jnp.asarray(maskp))
        else:
            carry0 = carry_tuple(carry0)

        if self.mesh is None:
            outs = fn(key, Qp, Gp, maskp, carry0)
        else:
            num_shards = int(np.prod([self.mesh.shape[a]
                                      for a in self.axis_names]))
            keys = jax.random.split(key, num_shards)
            outs = fn(keys, Qp, Gp, maskp, carry0)
        release(seed)

        # the controller state stays device-resident for the store; the
        # result itself resolves through ONE counted blocking fetch
        host = self._sync_fetch(outs)
        base = collect_result(host, order=order, crop=(n, m))
        res = ServiceMatchResult(**{f.name: getattr(base, f.name)
                                    for f in dataclasses.fields(MatchResult)})
        if self.warm_start:
            stored = self._path.keep(
                (outs["S_star"], outs["f_star"], outs["S_bar"]), res.carry)
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

        Same-bucket requests form one group, and one tier walk
        (``_walk``) serves the groups: Tier 0 revalidates every stored
        carry in one cheap launch, Tier 1 rebases similar carries for the
        misses, and only the residual requests launch the Tier-2 swarm
        (chunked to batch classes). Results come back in submission
        order; each request's ``latency_s`` is the wall time of the
        launches that actually served it, so an easy request no longer
        pays a hard neighbour's epochs.

        With ``pipelined=True`` (the default) one walk covers EVERY
        bucket group: each tier dispatches all its launches before
        anything blocks, so the host builds and enqueues group B's batch
        while the device still runs group A's, and each stage resolves
        through one batched fetch — an all-warm drain costs exactly one
        blocking host sync (``stats.host_syncs_per_drain``).
        ``pipelined=False`` walks one group at a time, fetches each
        launch as soon as it is dispatched, and stages carries through
        host numpy (one implicit sync per device-resident carry part):
        the serial arm the pooled path is checked against bitwise.
        """
        pending, self._pending = self._pending, []
        if not pending:
            return []
        self.stats.drains += 1
        groups: "OrderedDict[Tuple[int, int], List[int]]" = OrderedDict()
        for i, req in enumerate(pending):
            groups.setdefault(req.bucket, []).append(i)
        walks = ([list(groups.items())] if self.pipelined
                 else [[group] for group in groups.items()])
        results: List[Optional[ServiceMatchResult]] = [None] * len(pending)
        for walk in walks:
            for it in self._walk(pending, walk):
                it.result.latency_s = it.latency_s
                results[it.ticket] = it.result
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
        """Per-request intake of one bucket group: call/budget
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

    def _walk(self, pending: List[_PendingRequest],
              groups: List[Tuple[Tuple[int, int], List[int]]]
              ) -> List[_PipelineItem]:
        """Revalidate → similarity-rebase → swarm over bucket ``groups``
        ((bucket, tickets) pairs); returns every item, served.

        Each stage's launches are dispatched lazily from a generator and
        resolved by ``_resolve``. Tier 0/1 run only when
        ``_tiers_active()``: otherwise every item goes to the swarm with
        its exact carry or the cold prior. Store keys embed the bucket,
        so groups never interact, and within a group the tier order and
        miss order are the same whether the stages cover one group or
        all of them."""
        size = self.batch_classes[-1]
        tiers = self._tiers_active()
        state = []                   # (bucket, items, residual) per group

        def revalidate():            # Tier 0: every stored exact carry
            for bucket, idxs in groups:
                items = self._intake([pending[i] for i in idxs], idxs)
                residual = [it for it in items if it.carry is None]
                state.append((bucket, items, residual))
                if not tiers:
                    continue
                cand = [it for it in items if it.carry is not None]
                for chunk in _chunks(cand, size):
                    yield self._dispatch_revalidate(
                        bucket, chunk, [it.carry for it in chunk], tier=0,
                        miss_sink=residual)

        def rebase():                # Tier 1: the misses' nearest carries
            for bucket, _, residual in state:
                pairs = [(it, nb) for it in residual
                         if (nb := self._lookup_neighbor(it)) is not None]
                for chunk in _chunks(pairs, size):
                    yield self._dispatch_revalidate(
                        bucket, [it for it, _ in chunk],
                        [nb for _, nb in chunk], tier=1, miss_sink=[])

        def swarm():                 # Tier 2: the residual
            for bucket, items, _ in state:
                residual = [it for it in items if it.result is None]
                for chunk in _chunks(residual, size):
                    yield self._dispatch_swarm(bucket, chunk)

        self._resolve(revalidate())
        if tiers and self.similarity:
            self._resolve(rebase())
        self._resolve(swarm())
        return [it for _, items, _ in state for it in items]

    def _resolve(self, launches) -> None:
        """Resolve one stage. Pipelined, every launch is dispatched
        before ONE fetch covers them all; serial, each launch is fetched
        and applied as soon as it is dispatched."""
        if self.pipelined:
            self._apply_all(list(launches))
        else:
            for rec in launches:
                self._apply_all([rec])

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

    def _dispatch_revalidate(self, bucket, items: List[_PipelineItem],
                             carries: List[tuple], tier: int,
                             miss_sink: List) -> _LaunchRecord:
        """Enqueue one Tier-0/1 revalidation launch (no host sync): pad
        the batch, build its inputs, dispatch. The returned record
        resolves via ``_apply_revalidate`` once its outputs are
        fetched."""
        B = len(items)
        bclass = self._batch_class(B)
        tstats = self.stats.tier0 if tier == 0 else self.stats.tier1
        drain = items[0].req.drain

        with _span("dispatch", tier=tier, B=B, bclass=bclass, drain=drain):
            hits_before = self.stats.compile_cache_hits
            fn = self._executable_reval(bucket, bclass)
            compile_hit = self.stats.compile_cache_hits > hits_before

            _, Qb, Gb, maskb, carry0 = self._launch_inputs(
                "reval", bucket, items, carries, bclass)
            outs = fn(Qb, Gb, maskb, carry0)
            tstats.launches += 1
            tstats.checked += B
            return _LaunchRecord(kind="reval", bucket=bucket, items=items,
                                 tier=tier, B=B, bclass=bclass,
                                 compile_hit=compile_hit, outs=outs,
                                 carries=carries, miss_sink=miss_sink,
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
            # instead. A seed is held by its item until its swarm launch
            # is dispatched
            kept = {}
            if tier == 1:
                kept = self._path.keep_rebased(
                    rec.outs, host, [j for j in range(B) if ok[j]],
                    [j for j, it in enumerate(items)
                     if not ok[j] and it.carry is None])

            for j, it in enumerate(items):
                it.latency_s = done - it.t0
                if not ok[j]:
                    if j in kept:
                        it.seed = kept[j]
                        retain(it.seed)
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
                    carry = result_view(carries[j])
                    f_res = float(f_carry[j])
                else:
                    carry = (S_rb[j], fits[j], S_bar_rb[j])
                    f_res = float(fits[j])
                    self._store_carry(it.req, it.warm_key, kept[j],
                                      similar=True)
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
        req = _PendingRequest(key=like.key, workload_key=None,
                              order=np.arange(n_pad),
                              crop=(n_pad, m_pad), bucket=bucket,
                              Qp=Qp, Gp=Gp, maskp=maskp)
        return req, self._path.pad(bucket)

    def _launch_inputs(self, kind: str, bucket, items: List[_PipelineItem],
                       carries: List, bclass: int):
        """``(padded, Qb, Gb, maskb, carry0)`` of one launch of ``kind``:
        the items' requests and ``carries`` padded to ``bclass`` with pad
        slots, their stacks, and the carry inputs
        (``CarryPath.launch_inputs``); counts a donated launch."""
        padded, carries = [it.req for it in items], list(carries)
        if bclass > len(items):
            pad_req, pad_carry = self._pad_slot(bucket, padded[0],
                                                carries[0])
            padded += [pad_req] * (bclass - len(items))
            carries += [pad_carry] * (bclass - len(items))
        Qb = np.stack([r.Qp for r in padded])
        Gb = np.stack([r.Gp for r in padded])
        maskb, carry0 = self._path.launch_inputs(
            carries, np.stack([r.maskp for r in padded]))
        if self.mesh is None and self._donate_argnums(kind):
            self.stats.donated_launches += 1
        return padded, Qb, Gb, maskb, carry0

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

            # a slot's carry: its failed exact carry, its rebased seed,
            # or None for the cold prior
            padded, Qb, Gb, maskb, carry0 = self._launch_inputs(
                "batch", bucket, items,
                [it.carry if it.carry is not None else it.seed
                 for it in items], bclass)
            if padded[-1] is not padded[0] and bclass > B \
                    and self.cfg.early_exit and self.cfg.carry_fastpath:
                self.stats.pad_slots_frozen += bclass - B
            keys = [r.key for r in padded]
            if self.mesh is None and not all(isinstance(k, np.ndarray)
                                             for k in keys):
                # device keys stack device-side: np.asarray would be a
                # hidden sync per key
                keysb = jnp.stack(keys)
            else:
                keysb = np.stack([np.asarray(k) for k in keys])
            outs = fn(keysb, Qb, Gb, maskb, carry0)
            for it in items:
                # the launch has read the seed: give its row back
                release(it.seed)
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
                # the program selected each served mapping
                batch_results = collect_served_results(host, B, orders,
                                                       crops)
            else:
                batch_results = collect_batch_results(
                    host, rec.bclass, orders, crops)[:B]
            # every item's controller state is kept for warm starts
            stored = self._path.keep_served(rec.outs, batch_results)
            done = time.perf_counter()

            for j, it in enumerate(items):
                base = batch_results[j]
                res = ServiceMatchResult(
                    **{f.name: getattr(base, f.name)
                       for f in dataclasses.fields(MatchResult)})
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
