"""Pallas TPU kernel: the fused epoch-tail (epilogue) mega-kernel.

PR 7 fused the swarm inner loop (``kernels/epoch_fused.py``), but every
epoch still exited to a host-visible epilogue: two vmapped projections,
an Ullmann candidate refinement, two feasibility checks, a redundant
fitness recompute, and the elite-consensus reduction — ~7 separate XLA
dispatches round-tripping the full particle state ``S`` (N, n, m)
through HBM per epoch per problem. This kernel closes that fusion
frontier: the projections, refinement and feasibility of ``run_epoch``
run in one body, so an epoch is exactly two kernel launches
(``epoch_fused`` → this) with no host-visible intermediates between
them.

Per problem the body computes, with ``S`` read from HBM once:

  1. (optionally Gumbel-perturbed) **structured projection** ``M_a`` —
     the adjacency-guided constructive embed of ``ref.structured_project``,
     batched over particles with one-hot row/column selects;
  2. **greedy projection** ``M_proj`` + **Ullmann candidate
     refinement** (``refine_iters`` matrix-form sweeps) + structured
     re-projection → ``M_b``;
  3. per-particle **feasibility** of both (rows/cols injective,
     ``M G Mᵀ ⊇ Q``) and the ``feas_a ? M_a : M_b`` merge.

The **elite consensus** ``S̄`` over the threaded-in final fitness (the
fused epoch kernel's ``f_last``) is a top-k, a softmax and one weighted
sum; the ops layer computes it beside the launch with the ``ref``
oracle itself (``elite_consensus_reference``), so it is bitwise the
``ref`` value on every backend.

Grid: ``(P,)`` problems, same layout discipline as the fused epoch
kernel. Outputs are ``M_hat`` (P, N, n, m) int32 0/1 and ``feasible``
(P, N, 1, 1) int32 0/1; the ops layer casts to the public uint8/bool
dtypes.

Bitwise-parity engineering (the acceptance bar is bitwise equality
with the pre-fusion epilogue on the ``ref`` ↔ ``interpret`` pair):

* **No gather/scatter/dynamic index in-kernel.** ``.at[i, j].set``
  becomes a one-hot ``broadcasted_iota`` masked select, and reading row
  ``i`` a one-hot masked reduction (exact: values are 0/1 ints, whole
  rows, or a max over one live float).
* **Flat argmax decomposition.** ``ref.masked_argmax`` argmaxes the
  flattened (n·m,) array; in-kernel this is (row-max, row-argmax,
  argmax over row-maxes) — the same first-maximum in row-major order,
  so ``greedy_project`` picks identical pivots.
* **Exact integer matmuls.** Every contraction is 0/1 × 0/1 and runs
  through ``mxu.int_dot`` (bf16 operands, f32 accumulation — exact, and
  the form Mosaic accepts on a v5e); ``Q @ miss`` per particle becomes
  one (N, m, n) contraction plus a transpose.
* **Padding correctness**: construction loops run ``n_rows`` (logical)
  trips, and the feasibility row check masks padded all-zero rows with
  a static ``iota >= n_rows`` escape; padded mask columns are zero so
  they never enter any candidate set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.mxu import int_dot, vmem_limit

_NEG = float(jnp.finfo(jnp.float32).min)


# ---------------------------------------------------------------------------
# Loose-jnp oracles (the ``ref`` backend path — the bitwise ground truth)
# ---------------------------------------------------------------------------

def ullmann_refine_candidates_reference(S, M_proj, Q, G, mask, *,
                                        refine_threshold: float,
                                        refine_iters: int):
    """Candidate refinement of the pre-fusion epilogue, verbatim (ONE
    problem, batched over particles): threshold ∪ projection candidate
    set, ``refine_iters`` Ullmann sweeps, structured re-projection with
    an empty-row fallback to ``M_proj``. Returns ``(M_hat uint8,
    cand uint8)``."""
    rowmax = S.max(axis=-1, keepdims=True)
    cand = ((S >= refine_threshold * rowmax) | (M_proj > 0))
    cand = (cand & (mask[None] > 0)).astype(jnp.uint8)

    def sweep(_, c):
        return jax.vmap(ref.ullmann_refine_step,
                        in_axes=(0, None, None))(c, Q, G)

    cand = jax.lax.fori_loop(0, refine_iters, sweep, cand)
    S_restricted = S * cand.astype(S.dtype)
    M_hat = jax.vmap(lambda s, c: ref.structured_project(s, Q, G, c))(
        S_restricted, cand)
    empty_rows = cand.sum(-1, keepdims=True) == 0
    M_hat = jnp.where(empty_rows, M_proj, M_hat)
    return M_hat.astype(jnp.uint8), cand


def elite_consensus_reference(S_all, f_all, *, elite_k: int,
                              consensus_temp: float):
    """S̄: softmax-weighted average of the ``elite_k`` fittest particles
    (paper line 24), exactly as the pre-fusion ``elite_consensus``
    computed it (top_k → normalized softmax → einsum). Returns
    ``(weighted, weight_total, w)`` so the distributed matcher can psum
    the parts before dividing."""
    f_top, idx = jax.lax.top_k(f_all, elite_k)
    f_norm = (f_top - f_top[0]) / consensus_temp
    w = jax.nn.softmax(f_norm)
    S_top = S_all[idx]
    weighted = jnp.einsum("k,knm->nm", w, S_top)
    return weighted, jnp.sum(w), w


def epoch_finish_reference(S, f_final, gum, mask, Q, G, *,
                           gumbel_tau: float, refine_threshold: float,
                           refine_iters: int, elite_k: int,
                           consensus_temp: float):
    """Loose-jnp oracle of the fused epoch tail (ONE problem).

    This is the pre-fusion ``pso._epoch_finish`` verbatim — gumbel
    perturbation, structured + greedy projections, Ullmann candidate
    refinement, feasibility, elite consensus — with the redundant
    ``_fitness(S)`` recompute replaced by the threaded-in ``f_final``
    (the fused epoch kernel's last-step fitness, bitwise the same
    value). ``gum`` is the pre-drawn (N, n, m) Gumbel noise (``None``
    when ``gumbel_tau == 0`` — the tau = 0 path never draws). Returns
    ``(M_hat uint8 (N, n, m), feasible bool (N,), S_bar f32 (n, m))``.
    """
    if gumbel_tau > 0:
        S_proj_a = jnp.log(jnp.clip(S.astype(jnp.float32), 1e-9, None)) \
            + gumbel_tau * gum
    else:
        S_proj_a = S
    M_a = jax.vmap(lambda s: ref.structured_project(s, Q, G, mask))(S_proj_a)
    feas_a = jax.vmap(ref.is_feasible, in_axes=(0, None, None))(M_a, Q, G)
    M_proj = jax.vmap(lambda s: ref.greedy_project(s, mask))(S)
    M_b, _ = ullmann_refine_candidates_reference(
        S, M_proj, Q, G, mask, refine_threshold=refine_threshold,
        refine_iters=refine_iters)
    feas_b = jax.vmap(ref.is_feasible, in_axes=(0, None, None))(M_b, Q, G)
    M_hat = jnp.where(feas_a[:, None, None], M_a, M_b)
    feasible = feas_a | feas_b
    S_bar, _, _ = elite_consensus_reference(
        S, f_final, elite_k=elite_k, consensus_temp=consensus_temp)
    return M_hat.astype(jnp.uint8), feasible, S_bar


# ---------------------------------------------------------------------------
# The fused Pallas body
# ---------------------------------------------------------------------------

def _batched_structured(Sf, avail0, Qi, Gi, n_rows: int):
    """``ref.structured_project`` batched over the particle axis.

    ``Sf``: (N, n, m) f32 scores; ``avail0``: (N, n, m) int32 0/1
    initial candidates; ``Qi``/``Gi``: shared int32 graphs. Row ``i`` is
    read by a one-hot masked reduction and written by a one-hot select,
    in place of every ``.at[]`` scatter and dynamic index (exact: whole
    int rows / 0-1 writes, and a max over one live float). Loops
    ``n_rows`` trips — the LOGICAL query size, so row padding never adds
    iterations.
    """
    N, n, m = Sf.shape
    succ_need = jnp.sum(Qi, axis=1, keepdims=True)        # (n, 1) out-degree
    q_col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    q_row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (N, m), 1)
    row_iota3 = jax.lax.broadcasted_iota(jnp.int32, (N, n, m), 1)

    def body(i, state):
        avail, col_avail, out, img_rows = state
        row_i = row_iota3 == i
        preds = jnp.sum(jnp.where(q_col == i, Qi, 0), axis=1,
                        keepdims=True)                    # (n, 1) = Q[:, i]
        need = jnp.sum(preds, axis=0, keepdims=True)      # (1, 1)
        # support[p, j] = preds @ img_rows[p] — how many of i's placed
        # predecessors have an edge to j's image neighbourhood
        support = jnp.sum(img_rows * preds[None], axis=1)  # (N, m)
        # forward checking: free out-neighbours of candidate j
        free_out = int_dot(col_avail, Gi, (((1,), (1,)), ((), ())))
        avail_i = jnp.sum(jnp.where(row_i, avail, 0), axis=1)
        s_i = jnp.max(jnp.where(row_i, Sf, _NEG), axis=1)
        succ_i = jnp.sum(jnp.where(q_row == i, succ_need, 0), axis=0,
                         keepdims=True)                   # (1, 1)
        feas = ((avail_i > 0) & (support >= need) & (free_out >= succ_i))
        scores = jnp.where(feas, s_i, _NEG)               # (N, m)
        j = jnp.argmax(scores, axis=-1, keepdims=True)    # (N, 1)
        ok = jnp.max(scores, axis=-1, keepdims=True) > _NEG
        pick = (col_iota == j) & ok                       # (N, m) one-hot
        keep = 1 - pick.astype(jnp.int32)
        new_avail = avail * keep.reshape(N, 1, m)
        new_col = col_avail * keep
        new_out = jnp.where(row_i & pick.reshape(N, 1, m), 1, out)
        # img_rows[p, i] = ok ? Gi[j[p]] : 0 — row gather as a one-hot
        # matmul (picks exactly one 0/1 row)
        Gi_j = int_dot(pick, Gi, (((1,), (0,)), ((), ())))
        new_img = jnp.where(row_i, Gi_j.reshape(N, 1, m), img_rows)
        return new_avail, new_col, new_out, new_img

    col0 = jnp.ones((N, m), jnp.int32)
    out0 = jnp.zeros((N, n, m), jnp.int32)
    img0 = jnp.zeros((N, n, m), jnp.int32)
    _, _, out, _ = jax.lax.fori_loop(0, n_rows, body,
                                     (avail0, col0, out0, img0))
    return out


def _batched_greedy(Sf, avail0, n_rows: int):
    """``ref.greedy_project`` batched over particles: ``n_rows`` rounds
    of global masked argmax + row/column knockout. The flat (n·m,)
    argmax decomposes into (row-max, row-argmax, argmax over row-maxes)
    — the identical first-maximum in row-major order."""
    N, n, m = Sf.shape
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (N, n, 1), 1)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (N, 1, m), 2)

    def body(_, state):
        avail, out = state
        flat = jnp.where(avail != 0, Sf, _NEG)            # (N, n, m)
        row_max = jnp.max(flat, axis=-1, keepdims=True)   # (N, n, 1)
        row_arg = jnp.argmax(flat, axis=-1, keepdims=True)
        i_star = jnp.argmax(row_max, axis=1, keepdims=True)   # (N, 1, 1)
        take = jnp.max(row_max, axis=1, keepdims=True) > _NEG
        j_star = jnp.sum(jnp.where(row_iota == i_star, row_arg, 0),
                         axis=1, keepdims=True)           # (N, 1, 1)
        hit_row = (row_iota == i_star) & take             # (N, n, 1)
        hit_col = (col_iota == j_star) & take             # (N, 1, m)
        new_avail = (avail * (1 - hit_row.astype(jnp.int32))
                     * (1 - hit_col.astype(jnp.int32)))
        return new_avail, jnp.where(hit_row & hit_col, 1, out)

    out0 = jnp.zeros((N, n, m), jnp.int32)
    _, out = jax.lax.fori_loop(0, n_rows, body, (avail0, out0))
    return out


def _batched_sweep(Mi, Qi, Gi):
    """``ref.ullmann_refine_step`` batched: exact 0/1 dot_generals with
    the per-particle ``Q @ miss`` products built as (N, m, n)
    contractions plus a transpose."""
    support_out = int_dot(Mi, Gi, (((2,), (1,)), ((), ())))
    support_in = int_dot(Mi, Gi, (((2,), (0,)), ((), ())))
    miss_out = (support_out == 0).astype(jnp.int32)
    miss_in = (support_in == 0).astype(jnp.int32)
    viol_out = int_dot(miss_out, Qi, (((1,), (1,)), ((), ())))
    viol_in = int_dot(miss_in, Qi, (((1,), (0,)), ((), ())))
    viol = (jnp.transpose(viol_out, (0, 2, 1))
            + jnp.transpose(viol_in, (0, 2, 1)))
    return Mi * (viol == 0).astype(jnp.int32)


def _batched_feasible(Mi, Qi, Gi, n_rows: int):
    """``ref.is_feasible`` batched over particles, as an (N, 1, 1) 0/1
    column. ``Mi`` comes from a projection, so each row holds at most
    one 1 and ``M G`` stays 0/1 (exact on the MXU). Padded all-zero
    rows are excused from the rows-sum-to-one check via a static
    ``iota >= n_rows`` escape (vacuous unpadded)."""
    N, n, m = Mi.shape
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (N, n, 1), 1)
    rows_sum = jnp.sum(Mi, axis=2, keepdims=True)         # (N, n, 1)
    cols_sum = jnp.sum(Mi, axis=1, keepdims=True)         # (N, 1, m)
    rows_ok = jnp.min(((rows_sum == 1) | (row_iota >= n_rows)
                       ).astype(jnp.int32), axis=1, keepdims=True)
    cols_ok = jnp.min((cols_sum <= 1).astype(jnp.int32), axis=2,
                      keepdims=True)
    MG = int_dot(Mi, Gi, (((2,), (0,)), ((), ())))
    mapped = int_dot(MG, Mi, (((2,), (2,)), ((0,), (0,))))
    covers = jnp.min(jnp.min((mapped >= Qi[None]).astype(jnp.int32),
                             axis=2, keepdims=True), axis=1, keepdims=True)
    return rows_ok * cols_ok * covers


def _finish_kernel(s_ref, gum_ref, mask_ref, q_ref, g_ref,
                   m_out_ref, feas_out_ref, *, n_rows: int,
                   gumbel_tau: float, refine_threshold: float,
                   refine_iters: int):
    S = s_ref[0].astype(jnp.float32)                      # (N, n, m)
    # Mosaic casts uint8 only to int32, so every 8-bit input goes via int32
    avail_mask = (mask_ref[0].astype(jnp.int32) != 0).astype(jnp.int32)
    Qi = q_ref[0].astype(jnp.int32)
    Gi = g_ref[0].astype(jnp.int32)
    avail0 = jnp.broadcast_to(avail_mask[None], S.shape)

    # 1. (Gumbel-perturbed) structured projection — the τ = 0 branch is
    # static, so the dummy gum block is never read when tau is off.
    if gumbel_tau > 0:
        gum = gum_ref[0].astype(jnp.float32)
        S_proj_a = jnp.log(jnp.clip(S, 1e-9, None)) + gumbel_tau * gum
    else:
        S_proj_a = S
    M_a = _batched_structured(S_proj_a, avail0, Qi, Gi, n_rows)
    feas_a = _batched_feasible(M_a, Qi, Gi, n_rows)       # (N, 1, 1)

    # 2. greedy projection + Ullmann candidate refinement → M_b
    M_proj = _batched_greedy(S, avail0, n_rows)
    rowmax = jnp.max(S, axis=-1, keepdims=True)
    cand = (((S >= refine_threshold * rowmax) | (M_proj > 0))
            & (avail_mask[None] > 0)).astype(jnp.int32)
    cand = jax.lax.fori_loop(
        0, refine_iters, lambda _, c: _batched_sweep(c, Qi, Gi), cand)
    S_restricted = S * cand.astype(jnp.float32)
    M_b = _batched_structured(S_restricted, cand, Qi, Gi, n_rows)
    empty_rows = jnp.sum(cand, axis=-1, keepdims=True) == 0
    M_b = jnp.where(empty_rows, M_proj, M_b)
    feas_b = _batched_feasible(M_b, Qi, Gi, n_rows)

    # 3. merge + feasibility verdicts
    m_out_ref[0] = jnp.where(feas_a > 0, M_a, M_b)
    feas_out_ref[0] = jnp.maximum(feas_a, feas_b)


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "gumbel_tau", "refine_threshold",
                     "refine_iters", "interpret"))
def epoch_finish_pallas(S, gum, mask, Q, G, *, n_rows: int,
                        gumbel_tau: float, refine_threshold: float,
                        refine_iters: int, interpret: bool = False):
    """Fused batched epoch tail. ``S``: (P, N, n, m) final swarm;
    ``gum``: (P, N, n, m) pre-drawn Gumbel noise, or a (P, 1, 1, 1)
    dummy when ``gumbel_tau == 0`` (never read — keeps HBM accounting
    honest); ``mask``: (P, n, m); ``Q``: (P, n, n); ``G``: (P, m, m).
    ``n_rows`` is the LOGICAL query size (= n unpadded). Returns
    ``(M_hat (P, N, n, m) int32, feasible (P, N) int32)``; the ops
    layer casts to uint8/bool and crops.
    """
    P, N, n, m = S.shape
    kernel = functools.partial(
        _finish_kernel, n_rows=n_rows, gumbel_tau=gumbel_tau,
        refine_threshold=refine_threshold, refine_iters=refine_iters)
    particles = pl.BlockSpec((1, N, n, m), lambda p: (p, 0, 0, 0))
    m_hat, feas = pl.pallas_call(
        kernel,
        grid=(P,),
        in_specs=[
            particles,
            pl.BlockSpec((1,) + gum.shape[1:], lambda p: (p, 0, 0, 0)),
            pl.BlockSpec((1, n, m), lambda p: (p, 0, 0)),
            pl.BlockSpec((1, n, n), lambda p: (p, 0, 0)),
            pl.BlockSpec((1, m, m), lambda p: (p, 0, 0)),
        ],
        out_specs=[particles,
                   pl.BlockSpec((1, N, 1, 1), lambda p: (p, 0, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((P, N, n, m), jnp.int32),
            jax.ShapeDtypeStruct((P, N, 1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit(N * n * m * 4)),
        interpret=interpret,
    )(S.astype(jnp.float32), gum.astype(jnp.float32), mask, Q, G)
    return m_hat, feas.reshape(P, N)
