"""Pallas TPU kernel: the fused swarm-epoch mega-kernel.

Pre-fusion, one epoch of Algorithm 1 ran its K inner steps as a
``lax.scan`` over ~6 separate XLA ops (PSO update, optional requantize,
fitness, local/global best tracking), round-tripping the full particle
state ``(S, V, S_local, f_local)`` — three (N, n, m) float arrays plus a
fitness vector — through HBM on *every* inner step. At matcher problem
sizes the per-op launch overhead and that HBM traffic dominate epoch
latency (the RESPECT/edge-TPU setting the paper targets), so the loose
pipeline never approaches the MXU roofline.

This kernel runs the ENTIRE inner-step loop in one body: an in-kernel
``fori_loop`` over the K inner steps with ``S/V/S_local/f_local`` and
the pruned compatibility mask resident in VMEM for the whole epoch.
Only the epoch products ever leave the core: the final swarm ``S``
(consumed by projection/consensus), the global best ``(S_star,
f_star)`` and the per-step ``f_star`` trace. Per problem that replaces
``K × (3 reads + 3 writes)`` of the particle state with one read and
one write.

Grid: ``(P,)`` problems (the batched matcher's leading axis; a single
``run_epoch`` is P = 1), one grid step per problem so
``match_batch``/``revalidate_batch`` reuse the same body without a
vmap-of-pallas_call. Blocks are ``(1, N, n, m)`` for particle state,
``(1, n, m)`` for the controller state and mask, ``(1, K, N, r)`` for
the pre-drawn step randoms, and per-particle / per-problem scalars ride
as ``(1, N, 1, 1)`` / ``(1, 1, 1)`` blocks so the last two block dims
always equal the array's (Mosaic's tiling rule, for every batch size).
In-kernel, per-particle values stay ``(N, 1, 1)`` columns: Mosaic
aborts on 1-D values and on two-axis reductions of 3-D ones. VMEM at
cloud width (N = 64, a 19-tile window padded to n = 24 rows, m = 128
lanes): one particle block is 786 KB; the service's bucket for a full
128-engine target is m = 144, padded to 256 lanes (1.5 MB). The
scoped-VMEM limit is computed from the block (``mxu.vmem_limit``);
``tests/test_chip_compile.py`` compiles it for a v5e.

Bitwise-parity engineering (the acceptance bar is *bitwise* equality
with the loose scan on the ``ref`` ↔ ``interpret`` pair, including
``f_star_trace`` and RNG-draw order):

* **RNG**: ``jax.random`` cannot be called in-kernel, so the caller
  pre-draws ``r_all[k] = uniform(split(k_steps, K)[k], (N, 3))`` — a
  vmap over the same split keys the legacy scan consumed per step,
  which produces value-identical draws in the same order.
* **Normalization** uses real division (``S / max(row_sum, EPS)``)
  exactly like ``ref.pso_update`` — NOT the reciprocal-multiply of
  ``pso_update_pallas``, which is only allclose.
* **Global-best selection** replaces ``S_local[argmax(f_local)]`` with
  a one-hot masked sum (adding 0.0 is exact and S has no -0.0) and
  ``f_local[argmax]`` with ``max(f_local)`` (the same element).
* **Reductions** mirror the vmapped-ref lowering: the (N, n, n)
  residual is summed over rows, then over the row sums
  (``mxu.sum_last2``, the order ``ref.edge_fitness`` uses), row sums
  over the last axis only. The ops layer therefore runs interpret mode
  UNPADDED; padding (real TPU) preserves exactness of every integer op
  and is allclose on the fitness values (zero-padding can regroup f32
  reductions by a last ulp).

The quantized path (§3.4) mirrors ``ref.quantize_s`` /
``ref.row_normalize_quantized`` / ``ref.edge_fitness_quantized`` in
int32 (uint8 values, wider registers); its two matmuls are exact
integer contractions on the MXU (``mxu.int_dot`` / ``int_dot_wide``,
exact for up to 256 target columns), and the Q1.15 reciprocal-multiply
renormalize is elementwise, so S stays bitwise even padded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.mxu import int_dot, int_dot_wide, sum_last2, vmem_limit


def epoch_inner_reference(S, V, S_local, f_local, S_star, f_star, S_bar,
                          mask, Q, G, r_all, *, omega, c1, c2, c3, v_max,
                          quantized=False):
    """Loose-jnp oracle of the fused epoch loop (ONE problem).

    This is the pre-fusion ``run_epoch`` inner ``lax.scan`` verbatim,
    with the per-step PRNG draws hoisted into ``r_all`` (K, N, 3) —
    value-identical to splitting inside the scan, see module docstring.
    Composed from the same ``ref.*`` building blocks the dispatch
    layer's ``ref`` backend uses, so it is the bitwise ground truth the
    Pallas body is tested against. Returns
    ``(S_final, S_star, f_star, f_trace, f_last)`` where ``f_last`` is
    the per-particle fitness of ``S_final`` — the value the epoch
    epilogue previously recomputed from scratch. It initializes from
    the ``f_local`` input (which equals ``fitness(S)`` for the real
    caller, ``_epoch_start``), so a degenerate K = 0 epoch still
    returns the fitness of the state it hands the epilogue.
    """
    upd = functools.partial(ref.pso_update, omega=omega, c1=c1, c2=c2,
                            c3=c3, v_max=v_max)

    def fitness(S):
        if quantized:
            S_q = ref.quantize_s(S)
            f = jax.vmap(ref.edge_fitness_quantized,
                         in_axes=(0, None, None))(S_q, Q, G)
            return f.astype(jnp.float32) / (255.0 ** 4)
        return jax.vmap(ref.edge_fitness, in_axes=(0, None, None))(S, Q, G)

    def inner(state, r):
        S, V, S_local, f_local, S_star, f_star, _ = state
        S, V = jax.vmap(upd, in_axes=(0, 0, 0, None, None, None, 0))(
            S, V, S_local, S_star, S_bar, mask, r)
        if quantized:
            S_q = jax.vmap(ref.row_normalize_quantized, in_axes=(0, None))(
                ref.quantize_s(S), mask)
            S = ref.dequantize_s(S_q)
        f = fitness(S)
        improved = f > f_local
        S_local = jnp.where(improved[:, None, None], S, S_local)
        f_local = jnp.maximum(f, f_local)
        b = jnp.argmax(f_local)
        better = f_local[b] > f_star
        S_star = jnp.where(better, S_local[b], S_star)
        f_star = jnp.where(better, f_local[b], f_star)
        return (S, V, S_local, f_local, S_star, f_star, f), f_star

    f_last0 = f_local.astype(jnp.float32)
    (S, V, S_local, f_local, S_star, f_star, f_last), f_trace = jax.lax.scan(
        inner, (S, V, S_local, f_local, S_star, f_star, f_last0), r_all)
    return S, S_star, f_star, f_trace, f_last


def _epoch_kernel(r_ref, s_ref, v_ref, sl_ref, fl_ref, star_ref, fstar_ref,
                  sbar_ref, mask_ref, q_ref, g_ref,
                  s_out_ref, star_out_ref, fstar_out_ref, trace_ref,
                  flast_out_ref, *,
                  inner_steps: int, omega: float, c1: float, c2: float,
                  c3: float, v_max: float, quantized: bool):
    # Mosaic casts uint8 only to int32, so every 8-bit input goes via int32
    mask_i = mask_ref[0].astype(jnp.int32)         # (n, m)
    maskf = mask_i.astype(jnp.float32)
    maskq = mask_i != 0
    s_bar = sbar_ref[0].astype(jnp.float32)        # (n, m)
    N = s_ref.shape[1]
    K = trace_ref.shape[2]

    # per-row constants of the normalize fallback (ref.pso_update)
    mask_rows = jnp.sum(maskf, axis=-1, keepdims=True)          # (n, 1)
    uniform = maskf / jnp.maximum(mask_rows, 1.0)               # (n, m)
    # quantized-renormalize fallback (ref.row_normalize_quantized)
    mask_rows_q = jnp.sum(maskq.astype(jnp.int32), axis=-1, keepdims=True)
    uniform_q = jnp.where(
        maskq, jnp.clip(255 // jnp.maximum(mask_rows_q, 1), 1, 255), 0)

    q_i = q_ref[0].astype(jnp.int32)
    g_i = g_ref[0].astype(jnp.int32)
    q_f = q_i.astype(jnp.float32)
    g_f = g_i.astype(jnp.float32)

    def fitness(S):
        """Per-particle -||Q - S G Sᵀ||² as an (N, 1, 1) column."""
        if quantized:
            S_q = jnp.clip(jnp.round(S * 255.0), 0, 255).astype(jnp.int32)
            SG = int_dot(S_q, g_i, (((2,), (0,)), ((), ())))
            SGS = int_dot_wide(SG, S_q, (((2,), (2,)), ((0,), (0,))))
            resid = (q_i * (255 * 255) - SGS).astype(jnp.float32)
            return -sum_last2(resid * resid) / (255.0 ** 4)
        SG = jax.lax.dot_general(
            S, g_f, dimension_numbers=(((2,), (0,)), ((), ())))
        SGS = jax.lax.dot_general(
            SG, S, dimension_numbers=(((2,), (2,)), ((0,), (0,))))
        resid = q_f - SGS
        return -sum_last2(resid * resid)

    part_iota = jax.lax.broadcasted_iota(jnp.int32, (N, 1, 1), 0)
    step_iota = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def step(i, state):
        S, V, S_local, f_local, S_star, f_star, _, trace = state
        r = r_ref[0, i]                                # (N, r_pad)
        r0 = r[:, 0:1].reshape(N, 1, 1)
        r1 = r[:, 1:2].reshape(N, 1, 1)
        r2 = r[:, 2:3].reshape(N, 1, 1)
        # ref.pso_update, batched over the resident particle dim
        V = (omega * V
             + c1 * r0 * (S_local - S)
             + c2 * r1 * (S_star[None] - S)
             + c3 * r2 * (s_bar[None] - S))
        V = jnp.clip(V, -v_max, v_max)
        S = jnp.clip(S + V, 0.0, None) * maskf[None]
        row_sum = jnp.sum(S, axis=-1, keepdims=True)
        S = jnp.where(row_sum > ref.EPS,
                      S / jnp.maximum(row_sum, ref.EPS), uniform[None])
        if quantized:
            # straight-through requantize: quantize_s →
            # row_normalize_quantized (Q1.15 reciprocal) → dequantize_s,
            # all integer ops in int32 holding uint8-range values
            S_q = jnp.clip(jnp.round(S * 255.0), 0, 255).astype(jnp.int32)
            row = jnp.sum(S_q, axis=-1, keepdims=True)
            recip_q15 = jnp.round((1 << 15) / jnp.maximum(row, 1)
                                  ).astype(jnp.int32)
            prod = S_q * recip_q15 * 255
            out = jnp.clip((prod + (1 << 14)) >> 15, 0, 255)
            S_q = jnp.where(row > 0, out * maskq[None], uniform_q[None])
            S = S_q.astype(jnp.float32) / 255
        f = fitness(S)                                 # (N, 1, 1)
        improved = f > f_local
        S_local = jnp.where(improved, S, S_local)
        f_local = jnp.maximum(f, f_local)
        # global best: one-hot select of S_local[argmax] (exact — adding
        # 0.0 is exact and S has no -0.0); f_local[argmax] == max(f_local)
        b = jnp.argmax(f_local, axis=0)                # (1, 1)
        f_best = jnp.max(f_local, axis=0)              # (1, 1)
        S_best = jnp.sum(jnp.where(part_iota == b[None], S_local, 0.0),
                         axis=0)
        better = f_best > f_star
        S_star = jnp.where(better, S_best, S_star)
        f_star = jnp.where(better, f_best, f_star)
        trace = jnp.where(step_iota == i, f_star, trace)
        return S, V, S_local, f_local, S_star, f_star, f, trace

    # f_last carries the fitness of the CURRENT S (the value the epoch
    # epilogue consumes instead of recomputing); it initializes from the
    # f_local input, which is fitness(S) for the real caller.
    f_local0 = fl_ref[0].astype(jnp.float32)       # (N, 1, 1)
    state0 = (s_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
              sl_ref[0].astype(jnp.float32), f_local0,
              star_ref[0].astype(jnp.float32), fstar_ref[0], f_local0,
              jnp.zeros((1, K), jnp.float32))
    S, V, S_local, f_local, S_star, f_star, f_last, trace = \
        jax.lax.fori_loop(0, inner_steps, step, state0)
    s_out_ref[0] = S
    star_out_ref[0] = S_star
    fstar_out_ref[0] = f_star
    trace_ref[0] = trace
    flast_out_ref[0] = f_last


@functools.partial(
    jax.jit,
    static_argnames=("omega", "c1", "c2", "c3", "v_max", "quantized",
                     "interpret"))
def epoch_fused_pallas(S, V, S_local, f_local, S_star, f_star, S_bar,
                       mask, Q, G, r_all, *, omega: float, c1: float,
                       c2: float, c3: float, v_max: float,
                       quantized: bool = False, interpret: bool = False):
    """Fused batched epoch loop. Particle state ``S/V/S_local``:
    (P, N, n, m); ``f_local``: (P, N); controller ``S_star``/``S_bar``
    and ``mask``: (P, n, m); ``f_star``: (P,); ``Q``: (P, n, n); ``G``:
    (P, m, m); ``r_all``: (P, K, N, r) pre-drawn step randoms (only
    ``r[..., :3]`` is consumed — the ops layer lane-pads the rest).
    Returns ``(S_final (P, N, n, m), S_star (P, n, m), f_star (P,),
    f_trace (P, K), f_last (P, N))`` — ``f_last`` is the fitness of
    ``S_final``, threaded out so the epoch epilogue never recomputes
    it; the single-problem case is just P = 1.
    """
    P, N, n, m = S.shape
    K, r_dim = r_all.shape[1], r_all.shape[3]
    kernel = functools.partial(
        _epoch_kernel, inner_steps=K, omega=omega, c1=c1, c2=c2, c3=c3,
        v_max=v_max, quantized=quantized)
    particles = pl.BlockSpec((1, N, n, m), lambda p: (p, 0, 0, 0))
    per_particle = pl.BlockSpec((1, N, 1, 1), lambda p: (p, 0, 0, 0))
    plane = pl.BlockSpec((1, n, m), lambda p: (p, 0, 0))
    scalar = pl.BlockSpec((1, 1, 1), lambda p: (p, 0, 0))
    s_fin, star_fin, fstar_fin, trace, f_last = pl.pallas_call(
        kernel,
        grid=(P,),
        in_specs=[
            pl.BlockSpec((1, K, N, r_dim), lambda p: (p, 0, 0, 0)),
            particles, particles, particles, per_particle, plane, scalar,
            plane, plane,
            pl.BlockSpec((1, n, n), lambda p: (p, 0, 0)),
            pl.BlockSpec((1, m, m), lambda p: (p, 0, 0)),
        ],
        out_specs=[particles, plane, scalar,
                   pl.BlockSpec((1, 1, K), lambda p: (p, 0, 0)),
                   per_particle],
        out_shape=[
            jax.ShapeDtypeStruct((P, N, n, m), jnp.float32),
            jax.ShapeDtypeStruct((P, n, m), jnp.float32),
            jax.ShapeDtypeStruct((P, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((P, 1, K), jnp.float32),
            jax.ShapeDtypeStruct((P, N, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit(N * n * m * 4)),
        interpret=interpret,
    )(r_all.astype(jnp.float32), S, V, S_local,
      f_local.astype(jnp.float32).reshape(P, N, 1, 1), S_star,
      f_star.astype(jnp.float32).reshape(P, 1, 1), S_bar, mask, Q, G)
    return (s_fin, star_fin, fstar_fin.reshape(P), trace.reshape(P, K),
            f_last.reshape(P, N))
