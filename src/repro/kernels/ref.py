"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the semantic ground truth: each Pallas kernel's test sweeps
shapes/dtypes and asserts allclose against the function here. They are also
the default CPU execution path (jit'd XLA) used by the core library, since
Pallas interpret mode is only for validation.

All functions take a single particle's matrices; batch with ``jax.vmap``.
Shapes: Q (n, n), G (m, m), Mask/S/V/M (n, m).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.mxu import sum_last2

EPS = 1e-9


# ---------------------------------------------------------------------------
# 1. Edge-preserving fitness:  residual = || Q - S G S^T ||_F^2   (paper §3.3)
# ---------------------------------------------------------------------------

def edge_fitness(S: jax.Array, Q: jax.Array, G: jax.Array) -> jax.Array:
    """Float path. Returns the *fitness* f = -residual (higher is better)."""
    S = S.astype(jnp.float32)
    Qf = Q.astype(jnp.float32)
    Gf = G.astype(jnp.float32)
    SG = S @ Gf                      # (n, m)
    SGS = SG @ S.T                   # (n, n)
    resid = Qf - SGS
    # row sums first, then their sum: the order the fused kernels reduce in
    return -sum_last2(resid * resid)[0, 0]


def edge_fitness_quantized(S_q: jax.Array, Q: jax.Array, G: jax.Array,
                           scale: int = 255) -> jax.Array:
    """Fixed-point path (paper §3.4): S quantized to uint8 (S ≈ S_q/scale),
    binary Q/G in {0,1}; all MACs accumulate in int32, exactly as on the
    accelerator's int8 datapath. Residual is returned in *integer* units of
    (1/scale²); fitness = -residual so PSO ordering matches the float path.

    Note overflow headroom: entries of S_q G ≤ 255·m and of S_q G S_qᵀ ≤
    255²·m ≈ 6.5e4·m, so int32 accumulation is exact for m ≤ 32768 — far
    beyond any engine array. The final squared-residual reduction happens in
    f32 (the role of the hardware's wide accumulator tree) since the squares
    exceed int32 range.
    """
    S_i = S_q.astype(jnp.int32)
    Q_i = Q.astype(jnp.int32)
    G_i = G.astype(jnp.int32)
    SG = S_i @ G_i                   # int32 (n, m)
    SGS = SG @ S_i.T                 # int32 (n, n), units of 1/scale^2
    resid = (Q_i * (scale * scale) - SGS).astype(jnp.float32)
    return -sum_last2(resid * resid)[0, 0]


# ---------------------------------------------------------------------------
# 2. Ullmann refinement sweep (paper §3.3: feasibility via matrix products)
# ---------------------------------------------------------------------------

def ullmann_refine_step(M: jax.Array, Q: jax.Array, G: jax.Array) -> jax.Array:
    """One vectorized Ullmann refinement sweep for directed monomorphism.

    Keep candidate (i, j) iff
      out: ∀u with Q[i,u]=1  ∃v: M[u,v]=1 ∧ G[j,v]=1   (image has the out-edge)
      in:  ∀u with Q[u,i]=1  ∃v: M[u,v]=1 ∧ G[v,j]=1   (image has the in-edge)

    Expressed entirely as int32-accumulated matmuls + comparisons — the form
    the paper maps onto the MAC array.
    """
    Mi = M.astype(jnp.int32)
    Qi = Q.astype(jnp.int32)
    Gi = G.astype(jnp.int32)
    # support_out[u, j] = #candidates v of u with edge j->v in G
    support_out = Mi @ Gi.T                      # (n, m)
    # support_in[u, j]  = #candidates v of u with edge v->j in G
    support_in = Mi @ Gi                         # (n, m)
    miss_out = (support_out == 0).astype(jnp.int32)
    miss_in = (support_in == 0).astype(jnp.int32)
    # violations[i, j] = #neighbours u of i whose support at j is empty
    viol = Qi @ miss_out + Qi.T @ miss_in        # (n, m)
    return (M.astype(jnp.int32) * (viol == 0)).astype(M.dtype)


def _fixpoint(step, M: jax.Array, max_iters: int = 0) -> jax.Array:
    """Iterate ``step`` to a fixpoint (``max_iters=0``: while_loop until
    nothing changes — each productive iteration removes ≥ 1 candidate, so
    termination is bounded by the candidate count; > 0: fixed fori_loop)."""
    if max_iters and max_iters > 0:
        return jax.lax.fori_loop(0, max_iters, lambda _, m: step(m), M)

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        m, _ = state
        m2 = step(m)
        return m2, jnp.any(m2 != m)

    out, _ = jax.lax.while_loop(cond, body, (M, jnp.bool_(True)))
    return out


def ullmann_refine_fixpoint(M: jax.Array, Q: jax.Array, G: jax.Array,
                            max_iters: int = 0) -> jax.Array:
    """Iterate the sweep to fixpoint (bounded by n·m sweeps, far fewer in
    practice; ``max_iters=0`` means until convergence with a while_loop)."""
    return _fixpoint(lambda m: ullmann_refine_step(m, Q, G), M, max_iters)


def injectivity_prune(M: jax.Array) -> jax.Array:
    """All-different propagation on a candidate matrix.

    If a query row has exactly one surviving candidate column, no other row
    may use that column (mappings are injective). One application of the
    rule; iterate together with ``ullmann_refine_step`` to a fixpoint.
    Expressed as row/column reductions + elementwise ops only, so it lowers
    onto the same comparator/MAC datapath as the refinement sweep.
    """
    Mi = M.astype(jnp.int32)
    singleton_rows = (Mi.sum(axis=1, keepdims=True) == 1).astype(jnp.int32)
    claimed = (singleton_rows * Mi).sum(axis=0, keepdims=True)   # (1, m)
    keep = 1 - (claimed > 0).astype(jnp.int32) * (1 - singleton_rows * Mi)
    return (Mi * jnp.clip(keep, 0, 1)).astype(M.dtype)


def prune_mask_fixpoint(mask: jax.Array, Q: jax.Array, G: jax.Array,
                        max_iters: int = 0) -> jax.Array:
    """Shrink the global compatibility mask before any swarm runs.

    Alternates one Ullmann refinement sweep (1-hop arc consistency) with
    one injectivity-propagation step until nothing changes. This is the
    Ullmann half of the algorithm applied *globally* — on planted
    instances it often collapses most rows to singletons, turning the PSO
    into a local repair of the few remaining free rows. Empty rows simply
    make every particle infeasible, which is the correct answer.
    """
    return _fixpoint(
        lambda m: injectivity_prune(ullmann_refine_step(m, Q, G)),
        mask, max_iters)


def prune_fixpoint_count(mask: jax.Array, Q: jax.Array, G: jax.Array,
                         max_iters: int = 0):
    """``prune_mask_fixpoint`` with an explicit convergence counter.

    Semantic twin of the fused Pallas ``prune_fixpoint`` kernel: one fused
    iteration = one Ullmann refinement sweep followed by one injectivity-
    propagation step, iterated while anything changes and the sweep budget
    holds (``max_iters=0``: until convergence, bounded by the candidate
    count — each productive iteration removes ≥ 1 candidate). The pruned
    mask is identical to ``prune_mask_fixpoint``'s (a converged mask is a
    fixpoint of the step, so stopping early never changes the result).

    Returns ``(pruned_mask, sweeps)`` with ``sweeps`` the int32 number of
    fused iterations executed (including the final no-change one) — the
    prune-latency observable the scheduler's cost accounting consumes.
    """
    n, m = mask.shape
    bound = max_iters if max_iters and max_iters > 0 else n * m + 1

    def step(mk):
        return injectivity_prune(ullmann_refine_step(mk, Q, G))

    def cond(state):
        _, changed, it = state
        return changed & (it < bound)

    def body(state):
        mk, _, it = state
        mk2 = step(mk)
        return mk2, jnp.any(mk2 != mk), it + jnp.int32(1)

    out, _, sweeps = jax.lax.while_loop(
        cond, body, (mask, jnp.bool_(True), jnp.int32(0)))
    return out, sweeps


def is_feasible(M: jax.Array, Q: jax.Array, G: jax.Array) -> jax.Array:
    """Feasibility: M is a (partial-)injective 0/1 assignment matrix with one
    candidate per row, and M G Mᵀ covers Q (paper: "checking whether M̂ G M̂ᵀ
    contains the query graph Q")."""
    Mi = M.astype(jnp.int32)
    rows_ok = jnp.all(Mi.sum(axis=1) == 1)
    cols_ok = jnp.all(Mi.sum(axis=0) <= 1)
    mapped = Mi @ G.astype(jnp.int32) @ Mi.T
    covers = jnp.all(mapped >= Q.astype(jnp.int32))
    return rows_ok & cols_ok & covers


# ---------------------------------------------------------------------------
# 3. Fused PSO update (velocity + position + mask + row-normalize)
# ---------------------------------------------------------------------------

def pso_update(S: jax.Array, V: jax.Array, S_local: jax.Array,
               S_star: jax.Array, S_bar: jax.Array, mask: jax.Array,
               r: jax.Array, omega: float, c1: float, c2: float, c3: float,
               v_max: float = 1.0):
    """One PSO step for one particle (paper Algorithm 1 lines 8-11).

    r: (3,) uniform randoms for the cognitive/social/consensus terms.
    Returns (S_new, V_new); S_new is masked, non-negative, row-stochastic.
    """
    S = S.astype(jnp.float32)
    V = V.astype(jnp.float32)
    maskf = mask.astype(jnp.float32)
    V_new = (omega * V
             + c1 * r[0] * (S_local.astype(jnp.float32) - S)
             + c2 * r[1] * (S_star.astype(jnp.float32) - S)
             + c3 * r[2] * (S_bar.astype(jnp.float32) - S))
    V_new = jnp.clip(V_new, -v_max, v_max)
    S_new = jnp.clip(S + V_new, 0.0, None) * maskf
    row_sum = S_new.sum(axis=1, keepdims=True)
    # Rows whose mask is empty (or collapsed to zero) fall back to uniform
    # over the mask — mirrors the hardware's reciprocal-multiply normalizer
    # with a "row invalid" escape.
    mask_rows = maskf.sum(axis=1, keepdims=True)
    uniform = maskf / jnp.maximum(mask_rows, 1.0)
    S_new = jnp.where(row_sum > EPS, S_new / jnp.maximum(row_sum, EPS), uniform)
    return S_new, V_new


# ---------------------------------------------------------------------------
# 4. Masked argmax with index (the redesigned comparator accumulator tree)
# ---------------------------------------------------------------------------

def masked_argmax(X: jax.Array, mask: jax.Array):
    """Global argmax of X over entries where mask != 0.

    Returns (value, flat_index) with flat_index = i*m + j, matching the
    paper's tree accumulator that "outputs the index corresponding to the
    maximum value within a vector". If the mask is empty, value = -inf and
    index = 0.
    """
    neg = jnp.finfo(jnp.float32).min
    flat = jnp.where(mask.reshape(-1) != 0, X.reshape(-1).astype(jnp.float32),
                     neg)
    idx = jnp.argmax(flat)
    return flat[idx], idx.astype(jnp.int32)


def greedy_project(S: jax.Array, mask: jax.Array) -> jax.Array:
    """Project a relaxed S onto a discrete injective assignment M̂.

    Greedy global-argmax: repeatedly take the highest-probability feasible
    (tile, PE) pair, then knock out its row and column. n sequential steps of
    the masked-argmax primitive — exactly what the comparator-tree hardware
    executes. Returns a 0/1 (n, m) matrix; rows with no feasible PE stay 0
    (later failing the feasibility check, as they must).
    """
    n, m = S.shape
    Sf = S.astype(jnp.float32)

    def body(_, state):
        avail, out = state
        val, idx = masked_argmax(Sf, avail)
        i, j = idx // m, idx % m
        take = val > jnp.finfo(jnp.float32).min
        row_kill = jnp.where(jnp.arange(n) == i, 0, 1).astype(avail.dtype)
        col_kill = jnp.where(jnp.arange(m) == j, 0, 1).astype(avail.dtype)
        new_avail = avail * row_kill[:, None] * col_kill[None, :]
        new_out = out.at[i, j].set(jnp.where(take, 1, 0).astype(out.dtype))
        return (jnp.where(take, new_avail, avail),
                jnp.where(take, new_out, out))

    avail0 = (mask != 0).astype(jnp.uint8)
    out0 = jnp.zeros((n, m), dtype=jnp.uint8)
    _, out = jax.lax.fori_loop(0, n, body, (avail0, out0))
    return out


def structured_project(S: jax.Array, Q: jax.Array, G: jax.Array,
                       mask: jax.Array) -> jax.Array:
    """Adjacency-guided projection: embed the query DAG vertex-by-vertex in
    topological order (the preemptible-DAG builder emits tiles pre-sorted),
    assigning tile i to the highest-S target vertex that is (a) unused,
    (b) mask-compatible, and (c) adjacent in G to the images of ALL of i's
    already-placed predecessors.

    This is the Ullmann-guidance step done constructively: on sparse
    targets (engine meshes, degree ≤ 4) a structure-blind argmax projection
    almost never lands on a consistent sub-DAG, while this one inherits
    feasibility by construction (only the later *out*-edges still need the
    final verification). Rows with no consistent candidate stay zero (the
    feasibility check rejects them).
    """
    n, m = S.shape
    Sf = S.astype(jnp.float32)
    Qi = Q.astype(jnp.int32)
    Gi = G.astype(jnp.int32)
    neg = jnp.finfo(jnp.float32).min
    succ_need = Qi.sum(axis=1)                        # (n,) out-degree

    def body(i, state):
        avail, col_avail, out, img_rows = state
        # img_rows[p] = G[assign[p]] for assigned p (else zeros)
        preds = Qi[:, i]                              # (n,)
        need = preds.sum()
        support = preds @ img_rows                    # (m,) adj-pred count
        # forward checking: candidate j must keep enough *free*
        # out-neighbours for i's (all still unplaced) successors
        free_out = Gi @ col_avail                     # (m,)
        feas = ((avail[i] > 0) & (support >= need)
                & (free_out >= succ_need[i]))
        scores = jnp.where(feas, Sf[i], neg)
        j = jnp.argmax(scores)
        ok = scores[j] > neg
        col_kill = (jnp.arange(m) != j) | (~ok)
        new_avail = avail * col_kill[None, :].astype(avail.dtype)
        new_col = col_avail * col_kill.astype(col_avail.dtype)
        new_out = out.at[i, j].set(jnp.where(ok, 1, 0).astype(out.dtype))
        new_img = img_rows.at[i].set(
            jnp.where(ok, Gi[j], jnp.zeros((m,), jnp.int32)))
        return new_avail, new_col, new_out, new_img

    avail0 = (mask != 0).astype(jnp.uint8)
    col0 = jnp.ones((m,), jnp.int32)
    out0 = jnp.zeros((n, m), jnp.uint8)
    img0 = jnp.zeros((n, m), jnp.int32)
    _, _, out, _ = jax.lax.fori_loop(0, n, body,
                                     (avail0, col0, out0, img0))
    return out


# ---------------------------------------------------------------------------
# Quantization helpers (paper §3.4)
# ---------------------------------------------------------------------------

def quantize_s(S: jax.Array, scale: int = 255) -> jax.Array:
    """Uniform uint8 quantization of a row-stochastic S."""
    return jnp.clip(jnp.round(S.astype(jnp.float32) * scale), 0, 255
                    ).astype(jnp.uint8)


def dequantize_s(S_q: jax.Array, scale: int = 255) -> jax.Array:
    return S_q.astype(jnp.float32) / scale


def row_normalize_quantized(S_q: jax.Array, mask: jax.Array,
                            scale: int = 255) -> jax.Array:
    """Hardware-style row renormalization: divide-free.

    The accelerator replaces dividers with "multiplication by a
    reconfigurable reciprocal value" — we model a 16-bit fixed-point
    reciprocal (Q1.15) of each int32 row sum, then a fused
    multiply-round-shift back to uint8.
    """
    row = S_q.astype(jnp.int32).sum(axis=1, keepdims=True)      # int32
    rowf = jnp.maximum(row, 1)
    recip_q15 = jnp.round((1 << 15) / rowf).astype(jnp.int32)   # Q1.15 table
    prod = S_q.astype(jnp.int32) * recip_q15 * scale            # Q1.15 units
    out = (prod + (1 << 14)) >> 15                              # round
    out = jnp.clip(out, 0, 255).astype(jnp.uint8)
    maskq = (mask != 0)
    # empty rows -> uniform over mask (same escape as the float path)
    mask_rows = maskq.sum(axis=1, keepdims=True)
    uniform = jnp.where(
        maskq, jnp.clip(scale // jnp.maximum(mask_rows, 1), 1, 255), 0
    ).astype(jnp.uint8)
    return jnp.where(row > 0, out * maskq, uniform)
