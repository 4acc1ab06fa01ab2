"""TPU helpers shared by the fused kernels: exact integer contractions
on the matrix unit (MXU), the padding units, the fitness reduction order
and the scoped-VMEM budget.

Mosaic (the TPU kernel compiler) refuses int32 × int32 matmuls on a v5e.
Every integer contraction in the matcher kernels multiplies 0/1 matrices
or 8-bit values, and bf16 holds every integer in [0, 256] exactly. The
MXU multiplies bf16 operands exactly and accumulates in f32, which stays
exact while every partial sum is below 2**24. So these contractions run
as bf16 matmuls with f32 accumulation and give the same integers as the
int32 oracles in ``ref.py`` — bitwise, on every backend.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: Sublane count of a vreg: the fused kernels pad query rows to this.
SUBLANE = 8
#: Lane count of a vreg: target columns are padded to this.
LANE = 128


def int_dot(a: jax.Array, b: jax.Array, dimension_numbers) -> jax.Array:
    """``dot_general`` of integer arrays with entries in [0, 256].

    Exact while every output entry is below 2**24 (a 0/1 × 0/1
    contraction over any width a kernel sees, or 8-bit × 0/1 over fewer
    than 65,794 terms). Returns int32."""
    out = jax.lax.dot_general(
        a.astype(jnp.int32).astype(jnp.bfloat16),
        b.astype(jnp.int32).astype(jnp.bfloat16),
        dimension_numbers, preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)


def int_dot_wide(a: jax.Array, b: jax.Array, dimension_numbers
                 ) -> jax.Array:
    """:func:`int_dot` for ``a`` in [0, 2**16) and ``b`` in [0, 255].

    ``a`` is split into its high and low bytes, each contracted exactly;
    exact for contractions of at most 258 terms (255 · 255 · 258 <
    2**24), which covers every target of up to 256 engines. Longer
    contractions raise ``ValueError`` (shapes are static)."""
    (contract, _), _ = dimension_numbers
    terms = math.prod(a.shape[d] for d in contract)
    if terms > 258:
        raise ValueError(f"int_dot_wide is exact for at most 258 terms, "
                         f"got {terms}")
    hi = int_dot(a >> 8, b, dimension_numbers)
    lo = int_dot(a & 255, b, dimension_numbers)
    return hi * 256 + lo


def sum_last2(x: jax.Array) -> jax.Array:
    """Sum over the last two axes of a 3-D value, keeping them: (N, 1, 1).

    Two single-axis reductions, because Mosaic aborts on a two-axis
    reduction of a 3-D value. ``ref.edge_fitness`` reduces in the same
    order, so interpret mode stays bitwise equal to it."""
    return jnp.sum(jnp.sum(x, axis=-1, keepdims=True), axis=-2,
                   keepdims=True)



def vmem_limit(particle_block_bytes: int) -> int:
    """Scoped-VMEM limit for a fused kernel whose largest block is one
    problem's (N, n, m) f32 particle state.

    Compiles for a v5e measured the kernels' own temporaries at about 10
    such blocks, and the double-buffered input/output blocks of a grid
    over P > 1 problems add up to 8 more; 24 blocks leaves headroom.
    Never below the 16 MiB default, never above 96 MiB of the v5e's
    128 MiB VMEM."""
    return int(min(max(24 * particle_block_bytes, 16 << 20), 96 << 20))
