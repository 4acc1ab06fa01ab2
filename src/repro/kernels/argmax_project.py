"""Pallas TPU kernel: greedy argmax projection of a relaxed mapping S.

The paper redesigns the accelerator's tree-based accumulator with
"comparators and selectors, enabling the output of the index corresponding
to the maximum value within a vector" — precisely the primitive needed to
project the continuous S onto a discrete injective assignment M̂ (each tile
→ exactly one PE, each PE ← at most one tile).

The kernel runs the full greedy loop on-chip: grid = (n,) *sequential*
steps; S and the availability mask live in VMEM for the whole sweep (one
HBM read of S total, vs. n reads for a host-side loop). Step k:

    (i, j) = argmax over available entries of S
    M̂[i, j] = 1;  row i and column j become unavailable

Shapes up to (512, 512) f32 use ≈ 2 MB VMEM (S + avail scratch + output).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = jnp.finfo(jnp.float32).min


def _project_kernel(s_ref, mask_ref, o_ref, avail_ref):
    k = pl.program_id(0)
    n, m = s_ref.shape

    @pl.when(k == 0)
    def _init():
        avail_ref[...] = mask_ref[...].astype(jnp.float32)
        o_ref[...] = jnp.zeros_like(o_ref)

    sv = jnp.where(avail_ref[...] > 0.0, s_ref[...].astype(jnp.float32), _NEG)
    row_max = jnp.max(sv, axis=1)                       # (n,)
    i = jnp.argmax(row_max).astype(jnp.int32)
    val = jnp.max(row_max)
    row = jax.lax.dynamic_slice_in_dim(sv, i, 1, axis=0)  # (1, m)
    j = jnp.argmax(row[0]).astype(jnp.int32)
    take = val > _NEG

    rows = jax.lax.broadcasted_iota(jnp.int32, (n, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, m), 1)
    hit = (rows == i) & (cols == j) & take
    kill = ((rows == i) | (cols == j)) & take

    o_ref[...] = jnp.where(hit, jnp.ones_like(o_ref), o_ref[...])
    avail_ref[...] = jnp.where(kill, 0.0, avail_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def greedy_project_pallas(S: jax.Array, mask: jax.Array,
                          interpret: bool = False) -> jax.Array:
    """S: (n, m) f32; mask: (n, m) {0,1}. Returns M̂: (n, m) uint8."""
    n, m = S.shape
    out = pl.pallas_call(
        _project_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((n, m), lambda k: (0, 0)),
            pl.BlockSpec((n, m), lambda k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n, m), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((n, m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(S, mask)
    return out


def _masked_argmax_kernel(x_ref, mask_ref, val_ref, idx_ref):
    n, m = x_ref.shape
    xv = jnp.where(mask_ref[...] != 0, x_ref[...].astype(jnp.float32), _NEG)
    row_max = jnp.max(xv, axis=1)
    i = jnp.argmax(row_max).astype(jnp.int32)
    row = jax.lax.dynamic_slice_in_dim(xv, i, 1, axis=0)
    j = jnp.argmax(row[0]).astype(jnp.int32)
    val_ref[0, 0] = jnp.max(row_max)
    idx_ref[0, 0] = i * m + j


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_argmax_pallas(X: jax.Array, mask: jax.Array,
                         interpret: bool = False):
    """Single masked argmax (value, flat index) — the comparator-tree
    primitive itself, exposed for reuse and testing."""
    n, m = X.shape
    val, idx = pl.pallas_call(
        _masked_argmax_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n, m), lambda k: (0, 0)),
            pl.BlockSpec((n, m), lambda k: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda k: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda k: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(X, mask)
    return val[0, 0], idx[0, 0]
