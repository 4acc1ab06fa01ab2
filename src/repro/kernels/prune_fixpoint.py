"""Pallas TPU kernel: fused global-mask pre-prune to fixpoint.

The pre-prune (``ref.prune_mask_fixpoint``) is the cold-start workhorse of
the matcher: before any swarm runs, the global compatibility mask is shrunk
by alternating one Ullmann refinement sweep (1-hop arc consistency, four
{0,1}/small-int matmuls — the MXU path) with one injectivity-propagation
step (row/column reductions — the VPU path). Executed as loose jnp ops this
is 2·iters separate dispatches with an HBM round-trip for the mask between
every half-step; on planted instances the fixpoint takes 5–15 iterations,
so the pre-prune dominates cold-start latency.

This kernel fuses BOTH half-steps into one body and iterates them to
fixpoint *in-kernel*: the mask lives in registers/VMEM for the whole loop,
and an in-kernel convergence flag (``jnp.any(m' != m)`` as the
``lax.while_loop`` carry) stops the sweep the moment nothing changes — one
``pallas_call``, one HBM read of the mask, one write. The iteration count
is emitted per problem (a ``(1, 1, 1)`` block) as the prune-latency
observable the scheduler's cost accounting consumes.

Grid: ``(B,)`` problems, one per step; each problem carries its OWN Q/G
(the batched matcher prunes per-problem masks), so blocks are
``(1, n, m)`` / ``(1, n, n)`` / ``(1, m, m)``. VMEM at scheduler scale
(n, m ≤ 512 padded): mask + Q + G + int32 temporaries ≈ 5 MB.

Padding requirements (ops.py enforces): padded entries of the mask must be
0 and padded rows/cols of Q and G zero. Zero rows are never singletons
(row-sum 0 ≠ 1) and contribute no violations, so the fused step is exact
w.r.t. the unpadded semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mxu import int_dot


def _fused_step(mk: jax.Array, q: jax.Array, g: jax.Array) -> jax.Array:
    """One fused iteration: Ullmann refinement sweep + injectivity prune.

    All int32, mirroring ``ref.ullmann_refine_step`` /
    ``ref.injectivity_prune`` exactly so the Pallas kernel is bitwise
    interchangeable with the jnp oracle.
    """
    # -- refinement sweep: four exact 0/1 matmuls on the MXU --
    support_out = int_dot(mk, g, (((1,), (1,)), ((), ())))   # M @ G^T
    support_in = int_dot(mk, g, (((1,), (0,)), ((), ())))    # M @ G
    miss_out = (support_out == 0).astype(jnp.int32)
    miss_in = (support_in == 0).astype(jnp.int32)
    viol = (int_dot(q, miss_out, (((1,), (0,)), ((), ())))
            + int_dot(q, miss_in, (((0,), (0,)), ((), ()))))  # Q^T @ miss_in
    mk = mk * (viol == 0).astype(jnp.int32)
    # -- injectivity propagation: row/col reductions on the VPU --
    singleton_rows = (jnp.sum(mk, axis=1, keepdims=True) == 1
                      ).astype(jnp.int32)
    claimed = jnp.sum(singleton_rows * mk, axis=0, keepdims=True)  # (1, m)
    keep = 1 - (claimed > 0).astype(jnp.int32) * (1 - singleton_rows * mk)
    return mk * jnp.clip(keep, 0, 1)


def _prune_kernel(m_ref, q_ref, g_ref, o_ref, it_ref, *, max_iters: int):
    m0 = m_ref[0].astype(jnp.int32)                    # (n, m)
    q = q_ref[0].astype(jnp.int32)                     # (n, n)
    g = g_ref[0].astype(jnp.int32)                     # (m, m)
    n, m_dim = m0.shape
    # each productive iteration removes ≥ 1 candidate, so n·m + 1 bounds
    # the convergence loop when no explicit budget is given
    bound = max_iters if max_iters > 0 else n * m_dim + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < bound)

    def body(state):
        mk, _, it = state
        mk2 = _fused_step(mk, q, g)
        return mk2, jnp.any(mk2 != mk), it + jnp.int32(1)

    out, _, sweeps = jax.lax.while_loop(
        cond, body, (m0, jnp.bool_(True), jnp.int32(0)))
    o_ref[0] = out.astype(o_ref.dtype)
    it_ref[0] = jnp.full((1, 1), sweeps, jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def prune_fixpoint_pallas(M: jax.Array, Qb: jax.Array, Gb: jax.Array,
                          max_iters: int = 0, interpret: bool = False):
    """Fused batched pre-prune. M: (B, n, m) masks; Qb: (B, n, n);
    Gb: (B, m, m) per-problem graphs. Returns ``(pruned (B, n, m),
    sweeps (B,) int32)`` — the single-problem case is just B = 1.
    """
    B, n, m = M.shape
    kernel = functools.partial(_prune_kernel, max_iters=max_iters)
    out, sweeps = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n, m), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, n, n), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, m, m), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n, m), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, n, m), M.dtype),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(M, Qb, Gb)
    return out, sweeps.reshape(B)
