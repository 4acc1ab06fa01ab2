"""Pluggable kernel-backend layer: ONE seam between algorithm and kernels.

Everything in core/ (PSO epochs, the distributed matcher, the online
service) used to hand-wire its kernel calls — ``ref.structured_project``
here, ``ops.pso_update(backend=...)`` there — so adding an optimized
kernel meant touching every call site. This module replaces that with a
registry of :class:`KernelBackend` suites:

  * ``ref``       — jit'd pure-jnp oracles (kernels/ref.py). CPU default.
  * ``pallas``    — compiled Pallas TPU kernels (MXU-padded via ops.py).
  * ``interpret`` — the Pallas kernels in interpret mode (CPU validation).

Core code resolves a backend ONCE per (static) config —
``bk = backend.for_config(cfg)`` at trace time — and calls kernel entry
points on the suite; no ``ref.*`` / ``*_pallas`` import appears outside
``kernels/``.

**Selection precedence** (first match wins):

  1. explicit name passed to :func:`get_backend`,
  2. ``PSOConfig.backend`` when it is not ``"auto"``,
  3. the ``REPRO_KERNEL_BACKEND`` environment variable,
  4. the platform default (``pallas`` on TPU, else ``ref``).

The env override is read at *trace* time (backends are resolved where
jit-compiled programs are built), so set it before the first match call
of the process — it exists for deployments that cannot thread a config
through (benchmarks, smoke jobs, canaries).

**Registering a new kernel** is one step, not another hand-wired pair:
implement the reference path as a :class:`KernelBackend` method (append
its name to ``KERNEL_NAMES`` so the parity sweep in
``tests/test_backend.py`` refuses to pass until every backend agrees),
and route the optimized path through the same method — exactly how the
fused ``prune_fixpoint`` landed. Custom suites (a new accelerator, an
instrumented shim) subclass :class:`KernelBackend`, override what they
optimize, and call :func:`register_backend`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import Dict, Optional, Tuple

import jax

from repro.kernels import ops, ref

#: Canonical kernel entry points every backend must provide. The parity
#: test sweep iterates THIS tuple — adding a kernel without extending the
#: sweep fails tests, so the list cannot silently rot.
KERNEL_NAMES: Tuple[str, ...] = (
    "edge_fitness",
    "edge_fitness_quantized",
    "pso_update",
    "ullmann_refine_step",
    "greedy_project",
    "masked_argmax",
    "structured_project",
    "injectivity_prune",
    "is_feasible",
    "prune_fixpoint",
    "prune_fixpoint_batch",
    "epoch_fused",
    "epoch_fused_batch",
    "epoch_finish",
    "epoch_finish_batch",
    "quantize_s",
    "dequantize_s",
    "row_normalize_quantized",
)

ENV_VAR = "REPRO_KERNEL_BACKEND"

#: Dispatch tags the padding/dispatch layer (kernels/ops.py) understands.
_OPS_TAGS = ("ref", "pallas", "interpret", "auto")

#: Buffer-donation metadata for the service's executable calling
#: conventions, keyed by executable kind (see
#: ``MatcherService._resolve_executable``). The value is the argnums of
#: the stacked warm-carry pytree that is safe to donate: the batched
#: kinds receive freshly gathered/stacked carry arrays that nothing else
#: references, so XLA may update particle/controller state in place
#: (halving peak carry memory per launch). The single-problem ``match``
#: kind donates nothing — its carry input can alias a stored CarryStore
#: entry, and donating it would invalidate the store.
SERVICE_DONATABLE_ARGNUMS: Dict[str, Tuple[int, ...]] = {
    "match": (),            # fn(key,  Q,  G,  mask,  carry0)
    "batch": (4,),          # fn(keys, Qb, Gb, maskb, carry0)
    "reval": (3,),          # fn(Qb, Gb, maskb, carry0)
}


def donate_argnums_for(kind: str) -> Tuple[int, ...]:
    """Donatable argnums for one service-executable kind (empty tuple
    for unknown kinds — unknown calling conventions never donate)."""
    return SERVICE_DONATABLE_ARGNUMS.get(kind, ())


class KernelBackend:
    """One kernel suite: every matcher kernel behind a uniform surface.

    ``name`` is the registry key (normalized to lowercase — selection via
    config/env lowercases too, so any casing resolves); ``ops_backend``
    the dispatch tag handed to the padding/dispatch layer (kernels/ops.py)
    for the kernels that have a Pallas implementation. A custom suite
    that omits it inherits the platform default path (``"auto"``) for
    every kernel it does not override. Kernels without a Pallas
    implementation (the host-shaped constructive projection, feasibility,
    quantization helpers) run the shared jnp path on every backend —
    overriding them in a subclass is how an optimized version would land.

    Shapes are *logical* (unpadded); MXU-alignment padding happens inside
    the ops layer. Per-particle kernels are batched over a leading B axis
    exactly like ops.py; per-problem kernels (projection, feasibility,
    prune) take a single problem unless suffixed ``_batch``.
    """

    def __init__(self, name: str, ops_backend: Optional[str] = None):
        self.name = name.strip().lower()
        if ops_backend is None:
            ops_backend = self.name if self.name in _OPS_TAGS else "auto"
        if ops_backend not in _OPS_TAGS:
            raise ValueError(
                f"ops_backend {ops_backend!r} is not a dispatch tag the "
                f"ops layer understands ({_OPS_TAGS}); custom suites "
                f"should pick the tag their non-overridden kernels run "
                f"on (or omit it for the platform default)")
        self._ops = ops_backend

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"KernelBackend({self.name!r})"

    # -- fitness -----------------------------------------------------------

    def edge_fitness(self, S, Q, G):
        """Batched float fitness -||Q - S G Sᵀ||². S: (B, n, m) → (B,)."""
        return ops.edge_fitness(S, Q, G, backend=self._ops)

    def edge_fitness_quantized(self, S_q, Q, G, scale: int = 255):
        """Fixed-point fitness (uint8 S, int32 MACs). → (B,) f32."""
        return ops.edge_fitness_quantized(S_q, Q, G, scale=scale,
                                          backend=self._ops)

    # -- swarm update ------------------------------------------------------

    def pso_update(self, S, V, S_local, S_star, S_bar, mask, r, *,
                   omega, c1, c2, c3, v_max=1.0):
        """Fused velocity/position/mask/normalize step, batched."""
        return ops.pso_update(S, V, S_local, S_star, S_bar, mask, r,
                              omega=omega, c1=c1, c2=c2, c3=c3,
                              v_max=v_max, backend=self._ops)

    # -- refinement / pruning ----------------------------------------------

    def ullmann_refine_step(self, M, Q, G):
        """One refinement sweep, batched. M: (B, n, m) → (B, n, m)."""
        return ops.ullmann_refine_step(M, Q, G, backend=self._ops)

    def injectivity_prune(self, M):
        """All-different propagation on one (n, m) candidate matrix."""
        return ref.injectivity_prune(M)

    def prune_fixpoint(self, mask, Q, G, max_iters: int = 0):
        """Fused pre-prune of ONE (n, m) mask to fixpoint.

        Returns ``(pruned_mask, sweeps)`` — sweeps is the int32 number of
        fused (refine + injectivity) iterations executed.
        """
        out, sweeps = self.prune_fixpoint_batch(
            mask[None], Q[None], G[None], max_iters=max_iters)
        return out[0], sweeps[0]

    def prune_fixpoint_batch(self, maskb, Qb, Gb, max_iters: int = 0):
        """Fused pre-prune, batched over problems with per-problem Q/G."""
        return ops.prune_fixpoint(maskb, Qb, Gb, max_iters=max_iters,
                                  backend=self._ops)

    # -- fused epoch loop --------------------------------------------------

    def epoch_fused(self, S, V, S_local, f_local, S_star, f_star, S_bar,
                    mask, Q, G, r_all, *, omega, c1, c2, c3, v_max,
                    quantized: bool = False):
        """The entire K-step epoch inner loop for ONE problem.

        Particle state ``S/V/S_local`` (N, n, m) + ``f_local`` (N,)
        stays device-resident (VMEM on the fused path) across all K
        steps; ``r_all`` (K, N, 3) holds the pre-drawn per-step uniform
        randoms (same values, same order as drawing inside the loop).
        Returns ``(S_final, S_star, f_star, f_trace (K,), f_last (N,))``
        — ``f_last`` is the last inner step's per-particle fitness,
        threaded into the fused tail so the epilogue never recomputes
        it.
        """
        outs = self.epoch_fused_batch(
            S[None], V[None], S_local[None], f_local[None], S_star[None],
            f_star[None], S_bar[None], mask[None], Q[None], G[None],
            r_all[None], omega=omega, c1=c1, c2=c2, c3=c3, v_max=v_max,
            quantized=quantized)
        return tuple(x[0] for x in outs)

    def epoch_fused_batch(self, S, V, S_local, f_local, S_star, f_star,
                          S_bar, mask, Q, G, r_all, *, omega, c1, c2, c3,
                          v_max, quantized: bool = False):
        """Fused epoch loop batched over a leading problem axis P (the
        ``match_batch``/``revalidate_batch`` layout) — one kernel grid
        over problems, NOT a vmap of the single-problem entry point."""
        return ops.epoch_fused(S, V, S_local, f_local, S_star, f_star,
                               S_bar, mask, Q, G, r_all, omega=omega,
                               c1=c1, c2=c2, c3=c3, v_max=v_max,
                               quantized=quantized, backend=self._ops)

    # -- fused epoch tail --------------------------------------------------

    def epoch_finish(self, S, f_final, gum, mask, Q, G, *, gumbel_tau,
                     refine_threshold, refine_iters, elite_k,
                     consensus_temp):
        """The entire epoch epilogue for ONE problem, fused.

        (Gumbel-perturbed) structured projection, greedy projection +
        Ullmann candidate refinement and per-particle feasibility in one
        kernel body; the elite consensus beside it. ``S``: (N, n, m)
        final swarm; ``f_final``: (N,) the fused epoch kernel's
        last-step fitness (threaded through instead of recomputed);
        ``gum``: (N, n, m) pre-drawn Gumbel noise or ``None`` when
        ``gumbel_tau == 0``.
        Returns ``(M_hat (N, n, m) uint8, feasible (N,) bool,
        S_bar (n, m) f32)``.
        """
        outs = self.epoch_finish_batch(
            S[None], f_final[None], None if gum is None else gum[None],
            mask[None], Q[None], G[None], gumbel_tau=gumbel_tau,
            refine_threshold=refine_threshold, refine_iters=refine_iters,
            elite_k=elite_k, consensus_temp=consensus_temp)
        return tuple(x[0] for x in outs)

    def epoch_finish_batch(self, S, f_final, gum, mask, Q, G, *,
                           gumbel_tau, refine_threshold, refine_iters,
                           elite_k, consensus_temp):
        """Fused epoch tail batched over a leading problem axis P — one
        kernel grid over problems, so an epoch of ``run_epoch_batch``
        is exactly two launches (``epoch_fused_batch`` → this)."""
        return ops.epoch_finish(S, f_final, gum, mask, Q, G,
                                gumbel_tau=gumbel_tau,
                                refine_threshold=refine_threshold,
                                refine_iters=refine_iters,
                                elite_k=elite_k,
                                consensus_temp=consensus_temp,
                                backend=self._ops)

    def ullmann_refine_candidates(self, S, M_proj, Q, G, mask, *,
                                  refine_threshold, refine_iters):
        """Candidate refinement of paper line 20 for ONE problem,
        batched over particles: threshold ∪ projection candidate set,
        ``refine_iters`` sweeps through :meth:`ullmann_refine_step`,
        structured re-projection with an empty-row fallback to
        ``M_proj``. Returns ``(M_hat uint8, cand uint8)``. Composed
        from this suite's own sweep/projection kernels so a subclass
        overriding those automatically refines through them.
        """
        import jax
        import jax.numpy as jnp
        rowmax = S.max(axis=-1, keepdims=True)
        cand = ((S >= refine_threshold * rowmax) | (M_proj > 0))
        cand = (cand & (mask[None] > 0)).astype(jnp.uint8)

        def sweep(_, c):
            return self.ullmann_refine_step(c, Q, G)

        cand = jax.lax.fori_loop(0, refine_iters, sweep, cand)
        S_restricted = S * cand.astype(S.dtype)
        M_hat = jax.vmap(lambda s, c: self.structured_project(s, Q, G, c))(
            S_restricted, cand)
        empty_rows = cand.sum(-1, keepdims=True) == 0
        M_hat = jnp.where(empty_rows, M_proj, M_hat)
        return M_hat.astype(jnp.uint8), cand

    def elite_consensus(self, S_all, f_all, *, elite_k, consensus_temp):
        """S̄: softmax-weighted average of the ``elite_k`` fittest
        particles (paper line 24). Returns ``(weighted, weight_total,
        w)`` so the distributed matcher can psum the parts across
        devices before dividing. The fused tail computes the same
        reduction beside its launch; this standalone entry point serves
        the mesh builders and any caller outside the epoch hot path."""
        from repro.kernels.finish_fused import elite_consensus_reference
        return elite_consensus_reference(S_all, f_all, elite_k=elite_k,
                                         consensus_temp=consensus_temp)

    # -- projection / verification -----------------------------------------

    def greedy_project(self, S, mask):
        """Greedy argmax projection of one relaxed (n, m) S → uint8 M̂."""
        return ops.greedy_project(S, mask, backend=self._ops)

    def masked_argmax(self, X, mask):
        """Masked global argmax → (value, flat index)."""
        return ops.masked_argmax(X, mask, backend=self._ops)

    def structured_project(self, S, Q, G, mask):
        """Adjacency-guided constructive projection (one problem)."""
        return ref.structured_project(S, Q, G, mask)

    def is_feasible(self, M, Q, G):
        """Injective-assignment + edge-cover feasibility of one mapping."""
        return ref.is_feasible(M, Q, G)

    # -- quantization helpers ----------------------------------------------

    def quantize_s(self, S, scale: int = 255):
        """Quantize relaxed mappings S ∈ [0,1] to uint8 (× ``scale``)."""
        return ref.quantize_s(S, scale)

    def dequantize_s(self, S_q, scale: int = 255):
        """Inverse of :meth:`quantize_s`: uint8 S_q → float32 / scale."""
        return ref.dequantize_s(S_q, scale)

    def row_normalize_quantized(self, S_q, mask, scale: int = 255):
        """Divide-free row renormalization of a quantized (n, m) S_q
        (reciprocal-multiply model of the accelerator datapath)."""
        return ref.row_normalize_quantized(S_q, mask, scale)


# ---------------------------------------------------------------------------
# Registry + selection
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, KernelBackend] = {}


def register_backend(backend: KernelBackend) -> KernelBackend:
    """Register (or replace) a backend under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


def registered_backends() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


for _name in ("ref", "pallas", "interpret"):
    register_backend(KernelBackend(_name))
del _name


def _platform_default() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def resolve_backend_name(name: Optional[str] = None,
                         config=None) -> str:
    """Resolve the selection precedence to a concrete registry name.

    ``name``: explicit request (highest precedence). ``config``: anything
    with a ``backend`` attribute (``PSOConfig``); its value counts unless
    it is ``"auto"``/empty. Then the ``REPRO_KERNEL_BACKEND`` env var,
    then the platform default.
    """
    for cand in (name,
                 getattr(config, "backend", None),
                 os.environ.get(ENV_VAR)):
        if cand:
            cand = str(cand).strip().lower()
            if cand and cand != "auto":
                return cand
    return _platform_default()


def get_backend(name: Optional[str] = None, *, config=None) -> KernelBackend:
    """Look up the selected :class:`KernelBackend` (see precedence above)."""
    resolved = resolve_backend_name(name, config)
    try:
        return _REGISTRY[resolved]
    except KeyError:
        raise KeyError(
            f"unknown kernel backend {resolved!r}; registered: "
            f"{sorted(_REGISTRY)} (register custom suites via "
            f"repro.kernels.backend.register_backend)") from None


def for_config(cfg) -> KernelBackend:
    """The backend a (static) ``PSOConfig`` selects — the one call core/
    makes at trace time."""
    return get_backend(config=cfg)


def config_digest(cfg, *, extra: Tuple = ()) -> str:
    """Stable content digest of everything that shapes a compiled kernel.

    The on-disk AOT executable cache and the service snapshots both need
    a key that changes whenever a recompiled program could differ or a
    stored carry could stop being meaningful. This digest covers:

      * the **resolved backend suite name** (the full selection
        precedence, so flipping ``REPRO_KERNEL_BACKEND`` or
        ``PSOConfig.backend`` invalidates cached executables),
      * every field of the (frozen dataclass) config, sorted by name —
        any ``PSOConfig`` knob that alters the traced program changes
        the digest,
      * caller-supplied ``extra`` components (the service adds its shape
        bucketing parameters, jax version, and target platform).

    Returns a 16-hex-char prefix of the SHA-1 — collision-safe at cache
    sizes (dozens of executables), short enough for file names. Configs
    that are not dataclasses fall back to ``repr`` (stable for the
    ``PSOConfig``-like objects this repo passes)."""
    name = resolve_backend_name(config=cfg)
    if dataclasses.is_dataclass(cfg):
        fields = sorted(dataclasses.asdict(cfg).items())
    else:  # pragma: no cover - non-dataclass configs
        fields = repr(cfg)
    payload = repr((name, fields, tuple(extra)))
    return hashlib.sha1(payload.encode("utf-8")).hexdigest()[:16]
