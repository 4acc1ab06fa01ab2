"""Public kernel API with backend dispatch and MXU-alignment padding.

Backends:
  "ref"       — jit'd pure-jnp oracle (ref.py). Default on CPU.
  "pallas"    — compiled Pallas TPU kernels. Default on TPU.
  "interpret" — Pallas kernels in interpret mode (CPU validation only).
  "auto"      — "pallas" on TPU else "ref".

All entry points accept *logical* (unpadded) shapes; padding happens here
and is provably exact for every kernel (zero rows/cols contribute nothing —
see per-kernel notes). The fused prune, epoch and tail kernels pad query
rows to a multiple of 8 (the sublane count) and target columns to a
multiple of 128 (the lane count): a query window has at most a few dozen
tiles, and padding its rows to 128 would multiply their VMEM footprint.
The other kernels pad both to 128 (the MXU tile).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.argmax_project import (greedy_project_pallas,
                                          masked_argmax_pallas)
from repro.kernels.epoch_fused import (epoch_fused_pallas,
                                       epoch_inner_reference)
from repro.kernels.finish_fused import (elite_consensus_reference,
                                        epoch_finish_pallas,
                                        epoch_finish_reference)
from repro.kernels.mxu import LANE, SUBLANE
from repro.kernels.pso_fitness import (edge_fitness_pallas,
                                       edge_fitness_quantized_pallas)
from repro.kernels.prune_fixpoint import prune_fixpoint_pallas
from repro.kernels.pso_update import pso_update_pallas
from repro.kernels.ullmann_refine import ullmann_refine_step_pallas

MXU = 128


def resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


def _pad_to(x: jax.Array, sizes: Tuple[int, ...]) -> jax.Array:
    """Zero-pad trailing dims of x up to the given sizes."""
    pads = [(0, 0)] * (x.ndim - len(sizes))
    pads += [(0, s - d) for s, d in zip(sizes, x.shape[x.ndim - len(sizes):])]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _round_up(v: int, mult: int = MXU) -> int:
    return ((v + mult - 1) // mult) * mult


def _fused_pad(n: int, m: int) -> Tuple[int, int]:
    """Padded (rows, columns) of a fused kernel's (n, m) planes."""
    return _round_up(n, SUBLANE), _round_up(m, LANE)


# ---------------------------------------------------------------------------
# Fitness
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend",))
def edge_fitness(S: jax.Array, Q: jax.Array, G: jax.Array,
                 backend: str = "auto") -> jax.Array:
    """Batched fitness -||Q - S G S^T||^2. S: (B, n, m) -> (B,) f32."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return jax.vmap(ref.edge_fitness, in_axes=(0, None, None))(S, Q, G)
    n, m = S.shape[1], S.shape[2]
    np_, mp = _round_up(n), _round_up(m)
    Sp = _pad_to(S, (np_, mp))
    Qp = _pad_to(Q, (np_, np_))
    Gp = _pad_to(G, (mp, mp))
    return edge_fitness_pallas(Sp, Qp, Gp, interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("scale", "backend"))
def edge_fitness_quantized(S_q: jax.Array, Q: jax.Array, G: jax.Array,
                           scale: int = 255,
                           backend: str = "auto") -> jax.Array:
    """Fixed-point fitness (uint8 S, int32 accumulation). -> (B,) f32."""
    backend = resolve_backend(backend)
    if backend == "ref":
        f = jax.vmap(ref.edge_fitness_quantized,
                     in_axes=(0, None, None, None))(S_q, Q, G, scale)
        return f.astype(jnp.float32)
    n, m = S_q.shape[1], S_q.shape[2]
    np_, mp = _round_up(n), _round_up(m)
    Sp = _pad_to(S_q, (np_, mp))
    Qp = _pad_to(Q, (np_, np_))
    Gp = _pad_to(G, (mp, mp))
    return edge_fitness_quantized_pallas(
        Sp, Qp, Gp, scale=scale, interpret=(backend == "interpret"))


# ---------------------------------------------------------------------------
# Ullmann refinement
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend",))
def ullmann_refine_step(M: jax.Array, Q: jax.Array, G: jax.Array,
                        backend: str = "auto") -> jax.Array:
    """One refinement sweep, batched. M: (B, n, m) -> (B, n, m)."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return jax.vmap(ref.ullmann_refine_step,
                        in_axes=(0, None, None))(M, Q, G)
    B, n, m = M.shape
    np_, mp = _round_up(n), _round_up(m)
    Mp = _pad_to(M, (np_, mp))
    Qp = _pad_to(Q, (np_, np_))
    Gp = _pad_to(G, (mp, mp))
    out = ullmann_refine_step_pallas(Mp, Qp, Gp,
                                     interpret=(backend == "interpret"))
    return out[:, :n, :m]


@functools.partial(jax.jit, static_argnames=("max_iters", "backend"))
def prune_fixpoint(maskb: jax.Array, Qb: jax.Array, Gb: jax.Array,
                   max_iters: int = 0, backend: str = "auto"):
    """Fused global pre-prune to fixpoint, batched over problems.

    ``maskb``: (B, n, m) compatibility masks; ``Qb``: (B, n, n);
    ``Gb``: (B, m, m) — each problem prunes against its OWN graphs (the
    batched matcher's layout; broadcast Q/G for the shared case). One
    fused iteration = Ullmann refinement sweep + injectivity propagation;
    ``max_iters=0`` iterates to convergence. Returns ``(pruned maskb,
    sweeps (B,) int32)`` where ``sweeps`` counts the fused iterations
    executed (the prune-latency observable).
    """
    backend = resolve_backend(backend)
    if backend == "ref":
        return jax.vmap(
            lambda mk, Q, G: ref.prune_fixpoint_count(mk, Q, G, max_iters)
        )(maskb, Qb, Gb)
    B, n, m = maskb.shape
    np_, mp = _fused_pad(n, m)
    Mp = _pad_to(maskb, (np_, mp))
    Qp = _pad_to(Qb, (np_, np_))
    Gp = _pad_to(Gb, (mp, mp))
    out, sweeps = prune_fixpoint_pallas(Mp, Qp, Gp, max_iters=max_iters,
                                        interpret=(backend == "interpret"))
    return out[:, :n, :m], sweeps


# ---------------------------------------------------------------------------
# Fused PSO update
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("omega", "c1", "c2", "c3", "v_max", "backend"))
def pso_update(S, V, S_local, S_star, S_bar, mask, r,
               omega: float, c1: float, c2: float, c3: float,
               v_max: float = 1.0, backend: str = "auto"):
    """Batched fused PSO step. S/V/S_local: (B, n, m); S_star/S_bar/mask:
    (n, m); r: (B, 3) randoms. Returns (S_new, V_new)."""
    backend = resolve_backend(backend)
    if backend == "ref":
        fn = functools.partial(ref.pso_update, omega=omega, c1=c1, c2=c2,
                               c3=c3, v_max=v_max)
        return jax.vmap(fn, in_axes=(0, 0, 0, None, None, None, 0))(
            S, V, S_local, S_star, S_bar, mask, r)
    B, n, m = S.shape
    np_, mp = _round_up(n), _round_up(m)
    Sp = _pad_to(S, (np_, mp))
    Vp = _pad_to(V, (np_, mp))
    Lp = _pad_to(S_local, (np_, mp))
    starp = _pad_to(S_star, (np_, mp))
    barp = _pad_to(S_bar, (np_, mp))
    maskp = _pad_to(mask, (np_, mp))
    r8 = _pad_to(r.astype(jnp.float32), (8,))
    s_new, v_new = pso_update_pallas(
        Sp, Vp, Lp, starp, barp, maskp, r8,
        omega=omega, c1=c1, c2=c2, c3=c3, v_max=v_max,
        interpret=(backend == "interpret"))
    return s_new[:, :n, :m], v_new[:, :n, :m]


# ---------------------------------------------------------------------------
# Fused epoch loop (PSO update → requantize → fitness → best tracking × K)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("omega", "c1", "c2", "c3", "v_max", "quantized",
                     "backend"))
def epoch_fused(S, V, S_local, f_local, S_star, f_star, S_bar, mask, Q, G,
                r_all, omega: float, c1: float, c2: float, c3: float,
                v_max: float, quantized: bool = False,
                backend: str = "auto"):
    """The entire K-step epoch inner loop, batched over problems.

    Particle state ``S/V/S_local`` (P, N, n, m) + ``f_local`` (P, N)
    stay device-resident for the whole loop (VMEM-resident on the fused
    path); ``S_star``/``S_bar``/``mask`` (P, n, m), ``f_star`` (P,),
    ``Q`` (P, n, n), ``G`` (P, m, m), ``r_all`` (P, K, N, 3) pre-drawn
    uniforms. Returns ``(S_final, S_star, f_star, f_trace (P, K),
    f_last (P, N))`` — ``f_last`` is the last step's per-particle
    fitness, threaded into ``epoch_finish`` instead of recomputed.

    Padding note: interpret mode runs UNPADDED so the fused body is
    bitwise-equal to the vmapped ref scan (zero-padding regroups f32
    reductions by a last ulp); the compiled TPU path pads n to 8 rows
    and m to 128 lanes — exact for every integer op, allclose on the
    fitness values.
    Padded mask rows are all-zero, so they normalize to the zero
    fallback and contribute nothing to fitness.
    """
    backend = resolve_backend(backend)
    if backend == "ref":
        fn = functools.partial(epoch_inner_reference, omega=omega, c1=c1,
                               c2=c2, c3=c3, v_max=v_max,
                               quantized=quantized)
        return jax.vmap(fn)(S, V, S_local, f_local, S_star, f_star,
                            S_bar, mask, Q, G, r_all)
    kw = dict(omega=omega, c1=c1, c2=c2, c3=c3, v_max=v_max,
              quantized=quantized, interpret=(backend == "interpret"))
    if backend == "interpret":
        return epoch_fused_pallas(S, V, S_local, f_local, S_star, f_star,
                                  S_bar, mask, Q, G, r_all, **kw)
    P, N, n, m = S.shape
    np_, mp = _fused_pad(n, m)
    s_fin, star_fin, fstar_fin, trace, f_last = epoch_fused_pallas(
        _pad_to(S, (np_, mp)), _pad_to(V, (np_, mp)),
        _pad_to(S_local, (np_, mp)), f_local,
        _pad_to(S_star, (np_, mp)), f_star, _pad_to(S_bar, (np_, mp)),
        _pad_to(mask, (np_, mp)), _pad_to(Q, (np_, np_)),
        _pad_to(G, (mp, mp)), _pad_to(r_all.astype(jnp.float32), (8,)),
        **kw)
    return (s_fin[:, :, :n, :m], star_fin[:, :n, :m], fstar_fin, trace,
            f_last)


# ---------------------------------------------------------------------------
# Fused epoch tail (projections → Ullmann refine → feasibility → consensus)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("gumbel_tau", "refine_threshold", "refine_iters",
                     "elite_k", "consensus_temp", "backend"))
def epoch_finish(S, f_final, gum, mask, Q, G, gumbel_tau: float,
                 refine_threshold: float, refine_iters: int, elite_k: int,
                 consensus_temp: float, backend: str = "auto"):
    """The entire epoch epilogue, batched over problems.

    ``S``: (P, N, n, m) final swarm state; ``f_final``: (P, N) the fused
    epoch kernel's last-step fitness (threaded through — the epilogue
    never recomputes it); ``gum``: (P, N, n, m) pre-drawn Gumbel noise
    or ``None`` when ``gumbel_tau == 0``; ``mask``: (P, n, m); ``Q``:
    (P, n, n); ``G``: (P, m, m). Returns ``(M_hat (P, N, n, m) uint8,
    feasible (P, N) bool, S_bar (P, n, m) f32)``.

    The kernel computes ``M_hat`` and ``feasible``; the elite consensus
    ``S_bar`` runs beside it through the ``ref`` oracle on the unpadded
    swarm, so it is the ``ref`` value on every backend. The compiled
    TPU path pads n/m — exact for the integer projection / refinement /
    feasibility pipeline (the construction loops run the logical ``n``
    trips and padded mask columns never enter a candidate set).
    """
    backend = resolve_backend(backend)
    statics = dict(gumbel_tau=gumbel_tau,
                   refine_threshold=refine_threshold,
                   refine_iters=refine_iters)
    consensus = dict(elite_k=elite_k, consensus_temp=consensus_temp)
    if backend == "ref":
        fn = functools.partial(epoch_finish_reference, **statics,
                               **consensus)
        return jax.vmap(fn)(S, f_final, gum, mask, Q, G)
    P, N, n, m = S.shape
    if gum is None:
        # dummy block (never read when gumbel_tau == 0) — a (P, 1, 1, 1)
        # placeholder instead of a full (P, N, n, m) zeros array keeps
        # the kernel's HBM accounting honest
        gum = jnp.zeros((P, 1, 1, 1), jnp.float32)
    if backend == "interpret":
        m_hat, feas = epoch_finish_pallas(S, gum, mask, Q, G, n_rows=n,
                                          interpret=True, **statics)
    else:
        np_, mp = _fused_pad(n, m)
        gum_p = gum if gum.shape[2] == 1 else _pad_to(gum, (np_, mp))
        m_hat, feas = epoch_finish_pallas(
            _pad_to(S, (np_, mp)), gum_p, _pad_to(mask, (np_, mp)),
            _pad_to(Q, (np_, np_)), _pad_to(G, (mp, mp)), n_rows=n,
            interpret=False, **statics)
        m_hat = m_hat[:, :, :n, :m]
    S_bar = jax.vmap(lambda s, f: elite_consensus_reference(
        s, f, **consensus)[0])(S, f_final)
    return m_hat.astype(jnp.uint8), feas != 0, S_bar


# ---------------------------------------------------------------------------
# Projection / argmax
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend",))
def greedy_project(S: jax.Array, mask: jax.Array,
                   backend: str = "auto") -> jax.Array:
    """Project one relaxed (n, m) S to a discrete injective M̂ (uint8)."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return ref.greedy_project(S, mask)
    n, m = S.shape
    np_, mp = _round_up(n), _round_up(m)
    Sp = _pad_to(S, (np_, mp))
    maskp = _pad_to(mask, (np_, mp))
    out = greedy_project_pallas(Sp, maskp, interpret=(backend == "interpret"))
    return out[:n, :m]


@functools.partial(jax.jit, static_argnames=("backend",))
def masked_argmax(X: jax.Array, mask: jax.Array, backend: str = "auto"):
    """Masked argmax -> (value, flat index) over the *logical* shape."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return ref.masked_argmax(X, mask)
    n, m = X.shape
    np_, mp = _round_up(n), _round_up(m)
    Xp = _pad_to(X, (np_, mp))
    maskp = _pad_to(mask, (np_, mp))
    val, idx = masked_argmax_pallas(Xp, maskp,
                                    interpret=(backend == "interpret"))
    # translate padded flat index back to logical coordinates
    i, j = idx // mp, idx % mp
    return val, (i * m + j).astype(jnp.int32)
