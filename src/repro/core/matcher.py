"""Distributed IMMSched matcher: particles sharded over the device mesh.

This is the paper's "particles → engines" mapping lifted to pod scale:
every device runs a local swarm (vmap), and the *global controller* of the
paper becomes a collective schedule executed once per epoch:

  * global best  S*, f*  — all_gather of per-device bests + argmax select
  * consensus    S̄      — psum of per-device elite-weighted sums (a global
                           softmax over the union of local elites, computed
                           with a pmax-stabilized exponent)

The collectives are O(n·m·D) bytes per epoch vs O(N·K·n·m²) FLOPs of local
work, so the matcher scales ~linearly in devices — the multi-pod dry-run
compiles exactly this program on the 2×16×16 mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import pso
from repro.core.graphs import Graph, as_device_graphs
from repro.kernels import backend as kernel_backend


@dataclasses.dataclass
class MatchResult:
    mapping: Optional[np.ndarray]        # best feasible (n, m) or None
    feasible_count: int
    f_star: float
    f_star_trace: np.ndarray             # (T, K) global-best trajectory
    all_mappings: np.ndarray             # (T*N, n, m) projected mappings;
                                         # (0, n, m) where none crossed
                                         # (service Tier 0/1, and Tier 2
                                         # on a single device)
    all_feasible: np.ndarray             # (T*N,)
    all_fitness: np.ndarray              # (T*N,)
    carry: Optional[tuple] = None        # (S_star, f_star, S_bar) warm-start
    epochs_run: int = 0                  # epochs executed (< T on early exit)
    carry_verified: bool = False         # warm carry re-validated by one
                                         # projection (0-epoch fast path)
    prune_sweeps: int = 0                # fused pre-prune iterations run
                                         # (0 when prune_mask is off)

    @property
    def found(self) -> bool:
        return self.mapping is not None


def collect_result(outs, order=None, crop=None) -> MatchResult:
    """Host-side gather of a match-output pytree into a ``MatchResult``.

    ``order``: topological relabelling to undo (rows back to caller
    order). ``crop``: logical ``(n, m)`` to strip shape-bucket padding to
    before undoing the relabelling (used by the online service).

    Device output pytrees are fetched with ONE blocking ``device_get``
    up front (a single host sync for the whole result) instead of one
    implicit transfer per leaf; already-fetched host trees pass through
    untouched.
    """
    outs = jax.device_get(outs)
    feas = np.asarray(outs["feasible"]).reshape(-1)
    fit = np.asarray(outs["fitness"]).reshape(-1)
    maps = np.asarray(outs["mappings"])
    maps = maps.reshape(-1, maps.shape[-2], maps.shape[-1])
    if crop is not None:
        n, m = crop
        maps = maps[:, :n, :m]
    if order is not None:
        unperm = np.empty_like(maps)
        unperm[:, order, :] = maps
        maps = unperm
    best = None
    if feas.any():
        idx = np.where(feas)[0]
        best = maps[idx[np.argmax(fit[idx])]]
    carry_ok = bool(np.asarray(
        outs.get("carry_feasible", False)).reshape(-1)[-1])
    if best is None and carry_ok:
        # warm-carry fast path: every epoch was skipped, the re-validated
        # projection of the carried S* IS the mapping
        M_c = np.asarray(outs["carry_mapping"])
        M_c = M_c.reshape(-1, M_c.shape[-2], M_c.shape[-1])[-1]
        if crop is not None:
            M_c = M_c[:crop[0], :crop[1]]
        if order is not None:
            unperm = np.empty_like(M_c)
            unperm[order, :] = M_c
            M_c = unperm
        best = M_c
    return MatchResult(
        mapping=best,
        feasible_count=int(feas.sum()),
        f_star=float(np.asarray(outs["f_star"]).reshape(-1)[-1]),
        f_star_trace=np.asarray(outs["f_star_trace"]),
        all_mappings=maps, all_feasible=feas, all_fitness=fit,
        carry=(outs["S_star"], outs["f_star"], outs["S_bar"]),
        epochs_run=int(np.asarray(outs["epochs_run"]).reshape(-1)[-1]),
        carry_verified=carry_ok,
        prune_sweeps=int(np.asarray(outs.get("prune_sweeps", 0)
                                    ).reshape(-1)[-1]))


def split_batch_outs(outs, batch: int):
    """Split a ``match_batch`` output pytree into per-problem pytrees.

    The batch axis sits *after* the epoch axis on per-epoch leaves
    (mappings/feasible/fitness/f_star_trace are (T, B, ...)) and leads on
    the controller leaves (S_star/f_star/S_bar/epochs_run are (B, ...)).
    Each returned slice is exactly the pytree a single ``match`` call
    would produce, so it feeds straight into ``collect_result``.
    """
    per_epoch = {"mappings", "feasible", "fitness", "f_star_trace"}
    host = jax.device_get(dict(outs))   # ONE sync for the whole pytree
    return [{k: (v[:, b] if k in per_epoch else v[b])
             for k, v in host.items()}
            for b in range(batch)]


def collect_batch_results(outs, batch: int, orders=None, crops=None):
    """Host-side gather of batched match outputs into per-problem
    ``MatchResult``s (``orders``/``crops``: per-problem, or None)."""
    results = []
    for b, slice_b in enumerate(split_batch_outs(outs, batch)):
        results.append(collect_result(
            slice_b,
            order=None if orders is None else orders[b],
            crop=None if crops is None else crops[b]))
    return results


def collect_served_results(host, batch: int, orders, crops):
    """Host-side gather of a fetched ``pso.served_outs`` pytree into
    per-problem ``MatchResult``s for its first ``batch`` slots.

    The program has already selected each problem's served ``mapping``
    (B, n, m), so this crops and un-permutes one (n, m) mapping per
    problem; ``orders``/``crops`` are per problem. Mapping, counts and
    scalars equal ``collect_result`` on the full trace. No trace crosses:
    ``all_mappings`` is empty, (0, n, m) as the crop gives it, and
    ``carry`` is left for the caller to attach.
    """
    results = []
    for b in range(batch):
        n, m = crops[b]
        mapping = None
        if host["found"][b]:
            mapping = np.empty((n, m), np.uint8)
            mapping[orders[b], :] = host["mapping"][b, :n, :m]
        feas = host["feasible"][:, b].reshape(-1)
        results.append(MatchResult(
            mapping=mapping,
            feasible_count=int(feas.sum()),
            f_star=float(host["f_star"][b]),
            f_star_trace=host["f_star_trace"][:, b],
            all_mappings=np.zeros((0, n, m), np.uint8),
            all_feasible=feas,
            all_fitness=host["fitness"][:, b].reshape(-1),
            epochs_run=int(host["epochs_run"][b]),
            carry_verified=bool(host["carry_feasible"][b]),
            prune_sweeps=int(host["prune_sweeps"][b])))
    return results


def _fuse_global_best(S_star, f_star, axis_names):
    """Select the global-best particle without gathering every device's S.

    v1 all-gathered (D, n, m) — D×65 KB per device per epoch. v2 (§Perf):
    pmax the scalar fitness, then a *masked psum* ships only the winner's
    S (ties averaged — they have equal fitness), cutting the collective
    bytes by ~D/2×.
    """
    f_gmax = jax.lax.pmax(f_star, axis_names)
    is_best = (f_star >= f_gmax).astype(S_star.dtype)
    count = jax.lax.psum(is_best, axis_names)
    S_best = jax.lax.psum(S_star * is_best, axis_names) \
        / jnp.maximum(count, 1.0)
    return S_best, f_gmax


def _fuse_consensus(S, f, cfg, axis_names):
    """Global elite consensus across devices (paper's global controller)."""
    f_gmax = jax.lax.pmax(jnp.max(f), axis_names)
    k = max(1, int(round(cfg.elite_frac * S.shape[0])))
    f_top, idx = jax.lax.top_k(f, k)
    w = jnp.exp((f_top - f_gmax) / cfg.consensus_temp)
    weighted = jnp.einsum("k,knm->nm", w, S[idx])
    wsum = jnp.sum(w)
    weighted = jax.lax.psum(weighted, axis_names)
    wsum = jax.lax.psum(wsum, axis_names)
    return weighted / jnp.maximum(wsum, 1e-20)


def build_distributed_match(Q_shape: Tuple[int, int], mesh: Mesh,
                            cfg: pso.PSOConfig,
                            axis_names: Sequence[str] = ("data",)):
    """Returns a jit'd ``match(keys, Q, G, mask, carry0)`` running the full
    Algorithm 1 with the swarm sharded over ``axis_names`` of ``mesh``.

    ``keys`` must be (num_shards,) PRNG keys (one per device slice);
    ``carry0`` is a replicated ``(S_star, f_star, S_bar)`` warm-start (use
    ``pso.default_carry(mask)`` for a cold start). The result pytree
    mirrors ``pso.match`` with a leading shard axis on the per-particle
    outputs. The jitted function is named ``immsched_swarm``, as the
    service's single-device one is, so its XLA module reads
    ``jit_immsched_swarm``.

    The returned executable is tagged ``aot_exportable = False``: a
    ``jax.export``-serialized shard_map program pins the exporting
    process's device topology, so the service's on-disk AOT cache must
    not persist it (a restart on a different mesh would fail or skew the
    collective schedule). Mesh executables lean on JAX's persistent XLA
    compilation cache instead (see ``core/persist.py``).
    """
    axis_names = tuple(axis_names)

    def local_match(key, Q, G, mask, carry0):
        n, m = mask.shape
        if cfg.prune_mask:
            mask, prune_sweeps = kernel_backend.for_config(
                cfg).prune_fixpoint(mask, Q, G, cfg.prune_iters)
        else:
            prune_sweeps = jnp.int32(0)
        keys = jax.random.split(key[0], cfg.epochs)  # this shard's key

        if cfg.early_exit and cfg.carry_fastpath:
            # carry0/Q/G/mask are replicated, so every shard computes the
            # same verdict — the early-exit branch stays collective-safe
            M_c, carry_ok = pso.carry_fast_path(carry0, Q, G, mask, cfg)
        else:
            M_c = jnp.zeros((n, m), jnp.uint8)
            carry_ok = jnp.bool_(False)

        def run_one(carry, k):
            carry, outs = pso.run_epoch(carry, k, Q, G, mask, cfg)
            S_star, f_star, _ = carry
            # ---- global controller: fuse across the mesh ----
            S_star, f_star = _fuse_global_best(S_star, f_star, axis_names)
            S_bar = _fuse_consensus(outs.pop("S_final"), outs["fitness"],
                                    cfg, axis_names)
            # global best-so-far trajectory (replicated)
            outs["f_star_trace"] = jax.lax.pmax(outs["f_star_trace"],
                                                axis_names)
            return (S_star, f_star, S_bar), outs

        def all_found(found):
            # replicate the early-exit predicate so every shard takes the
            # same lax.cond branch (the live branch holds collectives)
            return jax.lax.pmax(found.astype(jnp.int32), axis_names) > 0

        (S_star, f_star, S_bar), outs, epochs_run = pso.scan_epochs(
            run_one, carry0, keys, n, m, cfg, all_found=all_found,
            done0=carry_ok)
        outs["S_star"] = S_star
        outs["f_star"] = f_star
        outs["S_bar"] = S_bar
        outs["epochs_run"] = epochs_run
        outs["carry_mapping"] = M_c
        outs["carry_feasible"] = carry_ok
        outs["prune_sweeps"] = prune_sweeps
        return outs

    shard_axes = P(axis_names)
    in_specs = (shard_axes, P(), P(), P(), (P(), P(), P()))
    out_specs = dict(
        mappings=P(None, axis_names), feasible=P(None, axis_names),
        fitness=P(None, axis_names), f_star_trace=P(),
        S_star=P(), f_star=P(), S_bar=P(), epochs_run=P(),
        carry_mapping=P(), carry_feasible=P(), prune_sweeps=P())

    fn = jax.shard_map(local_match, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return _mesh_jit("immsched_swarm", fn)


def _mesh_jit(name: str, fn):
    """``jax.jit`` of ``fn`` under ``name`` (its XLA module reads
    ``jit_<name>``), tagged so the AOT persistence layer skips
    ``jax.export`` for it (the serialized program would pin this
    process's device count/topology); see ``build_distributed_match``."""
    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    jitted = jax.jit(named)
    jitted.aot_exportable = False
    return jitted


def build_distributed_match_batch(Q_shape: Tuple[int, int], mesh: Mesh,
                                  cfg: pso.PSOConfig,
                                  axis_names: Sequence[str] = ("data",),
                                  batch: int = 1):
    """Returns a jit'd ``match(keys, Qb, Gb, maskb, carry0)`` solving a
    stacked batch of B problems on the mesh.

    ``keys`` is (B,) PRNG keys (one per problem); ``Qb``/``Gb``/``maskb``
    are stacked on the leading problem axis and ``carry0`` holds stacked
    per-problem warm-start carries. Two regimes:

      * **problem-axis sharding** (B ≥ devices and divisible): each device
        solves B/D whole problems locally — zero collectives, and each
        problem's result is bit-identical to the single-device path.
      * **per-problem particle sharding** (small B): falls back to the
        collective-fused ``build_distributed_match`` executed per problem
        (unrolled — B is static), stacking results on the problem axis.

    Output layout matches ``pso.match_batch`` (problem axis after the
    epoch axis on per-epoch leaves, leading elsewhere). Either way the
    jitted function is named ``immsched_swarm_batch``.
    """
    axis_names = tuple(axis_names)
    num_shards = int(np.prod([mesh.shape[a] for a in axis_names]))

    if batch >= num_shards and batch % num_shards == 0:
        def local_match(keys, Qb, Gb, maskb, carry0):
            return pso._match_batch_body(keys, Qb, Gb, maskb, cfg, carry0)

        shard_b = P(axis_names)
        in_specs = (shard_b, shard_b, shard_b, shard_b,
                    (shard_b, shard_b, shard_b))
        out_specs = dict(
            mappings=P(None, axis_names), feasible=P(None, axis_names),
            fitness=P(None, axis_names), f_star_trace=P(None, axis_names),
            S_star=shard_b, f_star=shard_b, S_bar=shard_b,
            epochs_run=shard_b, carry_mapping=shard_b,
            carry_feasible=shard_b, prune_sweeps=shard_b)
        fn = jax.shard_map(local_match, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return _mesh_jit("immsched_swarm_batch", fn)

    per_problem = build_distributed_match(Q_shape, mesh, cfg, axis_names)
    per_epoch = ("mappings", "feasible", "fitness", "f_star_trace")

    def fn(keys, Qb, Gb, maskb, carry0):
        outs_list = []
        for b in range(batch):
            kb = jax.random.split(keys[b], num_shards)
            cb = jax.tree_util.tree_map(lambda x: x[b], carry0)
            outs_list.append(per_problem(kb, Qb[b], Gb[b], maskb[b], cb))
        return {k: jnp.stack([o[k] for o in outs_list],
                             axis=1 if k in per_epoch else 0)
                for k in outs_list[0]}

    return _mesh_jit("immsched_swarm_batch", fn)


def build_distributed_revalidate_batch(Q_shape: Tuple[int, int], mesh: Mesh,
                                       cfg: pso.PSOConfig,
                                       axis_names: Sequence[str] = ("data",),
                                       batch: int = 1):
    """Returns a jit'd ``revalidate(Qb, Gb, maskb, carry0)`` running the
    tiered pipeline's cheap stage (carry rebase + one structured
    projection + feasibility per problem) on the mesh.

    Revalidation has no swarm and no collectives, so the two regimes are
    both embarrassingly parallel:

      * **problem-axis sharding** (B ≥ devices and divisible): each device
        revalidates B/D carries locally;
      * **replicated fallback** (small B): every device computes the whole
        (tiny) batch — one projection per problem is far below the cost of
        re-sharding, and the replicated outputs keep the calling
        convention identical.

    The jitted function is named ``immsched_revalidate``.
    """
    axis_names = tuple(axis_names)
    num_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    def local_reval(Qb, Gb, maskb, carry0):
        return pso._revalidate_batch_body(Qb, Gb, maskb, cfg, carry0)

    if batch >= num_shards and batch % num_shards == 0:
        shard_b = P(axis_names)
        in_specs = (shard_b, shard_b, shard_b,
                    (shard_b, shard_b, shard_b))
        out_specs = dict(mapping=shard_b, ok=shard_b, ok_rebase=shard_b,
                         fitness=shard_b, S_star=shard_b, S_bar=shard_b,
                         prune_sweeps=shard_b, f_carry=shard_b)
    else:
        in_specs = (P(), P(), P(), (P(), P(), P()))
        out_specs = dict(mapping=P(), ok=P(), ok_rebase=P(), fitness=P(),
                         S_star=P(), S_bar=P(), prune_sweeps=P(),
                         f_carry=P())
    fn = jax.shard_map(local_reval, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return _mesh_jit("immsched_revalidate", fn)


class IMMSchedMatcher:
    """High-level matcher API.

    Single-device by default; pass a mesh + axis names for the sharded
    version (each mesh slice runs ``cfg.num_particles`` particles).
    """

    def __init__(self, cfg: Optional[pso.PSOConfig] = None,
                 mesh: Optional[Mesh] = None,
                 axis_names: Sequence[str] = ("data",)):
        self.cfg = cfg or pso.PSOConfig()
        self.mesh = mesh
        self.axis_names = tuple(axis_names)

    def match(self, query: Graph, target: Graph,
              key: Optional[jax.Array] = None,
              carry0=None) -> MatchResult:
        from repro.core.graphs import topological_relabel
        query, order = topological_relabel(query)
        self._order = order
        Q, G, mask = as_device_graphs(query, target)
        if key is None:
            key = jax.random.PRNGKey(0)
        if carry0 is None:
            carry0 = pso.default_carry(mask)
        if self.mesh is None:
            outs = pso.match(key, Q, G, mask, self.cfg, carry0)
        else:
            num_shards = int(np.prod([self.mesh.shape[a]
                                      for a in self.axis_names]))
            keys = jax.random.split(key, num_shards)
            fn = build_distributed_match(Q.shape, self.mesh, self.cfg,
                                         self.axis_names)
            outs = fn(keys, Q, G, mask, carry0)
        return self._collect(outs)

    def _collect(self, outs) -> MatchResult:
        return collect_result(outs, order=getattr(self, "_order", None))
