"""Pallas TPU kernel: fused edge-preserving PSO fitness  -||Q - S G S^T||^2.

This is the matcher's compute hot-spot (two back-to-back matmuls per particle
per evaluation) and the computation the paper explicitly maps onto the
accelerator's MAC array, in both float and uint8/int32 fixed-point form
(paper §3.4).

Tiling: grid = (B particles, n/TILE_N query-row tiles). Per grid step the
kernel holds in VMEM:
  * one (TILE_N, m) row-block of this particle's S,
  * the particle's full S (n, m) for the S^T contraction,
  * the full target adjacency G (m, m),
  * the (TILE_N, n) row-block of Q,
and accumulates the block's squared residual into a (1, 1) output cell.
The row-tile loop is sequential per particle ("arbitrary"), particles are
parallel. Both matmuls hit the MXU with hardware-aligned (128-multiple)
dims — ops.py pads n and m.

VMEM budget (f32, n = m = 512): 512*512*4 * 2 (S, G) + 128*512*4 (block)
+ 128*512*4 (Q block) ≈ 2.6 MB — comfortably inside the ~16 MB/core VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mxu import int_dot, int_dot_wide, sum_last2

TILE_N = 128


def _fitness_kernel(s_blk_ref, s_full_ref, q_blk_ref, g_ref, o_ref):
    """Float path. Shapes: s_blk (1, TILE_N, m), s_full (1, n, m),
    q_blk (TILE_N, n), g (m, m), o (1, 1, 1)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s_blk = s_blk_ref[0].astype(jnp.float32)           # (TILE_N, m)
    s_full = s_full_ref[0].astype(jnp.float32)         # (n, m)
    # Mosaic casts uint8 only to int32, so 8-bit graphs go via int32
    g = g_ref[...].astype(jnp.int32).astype(jnp.float32)      # (m, m)
    q = q_blk_ref[...].astype(jnp.int32).astype(jnp.float32)  # (TILE_N, n)

    sg = jnp.dot(s_blk, g, preferred_element_type=jnp.float32)
    # (TILE_N, n) = (TILE_N, m) @ (n, m)^T
    sgs = jax.lax.dot_general(sg, s_full,
                              dimension_numbers=(((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    r = q - sgs
    o_ref[0] += -sum_last2(r * r)


def _fitness_kernel_quantized(s_blk_ref, s_full_ref, q_blk_ref, g_ref, o_ref,
                              *, scale: int):
    """Fixed-point path: S is uint8 (≈ S*scale), Q/G are {0,1} uint8.

    Both matmuls are exact integer contractions on the MXU
    (``mxu.int_dot``: 8-bit × 0/1, then the wide partials split into
    bytes against 8-bit S). The squared-residual reduction accumulates in
    f32 — the role of the hardware's wide accumulator tree. Residual is
    in units of 1/scale², so fitness ordering matches the float kernel.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s_blk = s_blk_ref[0].astype(jnp.int32)             # (TILE_N, m)
    s_full = s_full_ref[0].astype(jnp.int32)           # (n, m)
    g = g_ref[...].astype(jnp.int32)                   # (m, m)
    q = q_blk_ref[...].astype(jnp.int32)               # (TILE_N, n)

    sg = int_dot(s_blk, g, (((1,), (0,)), ((), ())))
    sgs = int_dot_wide(sg, s_full, (((1,), (1,)), ((), ())))
    r = (q * (scale * scale) - sgs).astype(jnp.float32)
    o_ref[0] += -sum_last2(r * r)


def _grid_specs(B: int, n: int, m: int, s_dtype, q_dtype):
    n_tiles = pl.cdiv(n, TILE_N)
    grid = (B, n_tiles)
    in_specs = [
        pl.BlockSpec((1, TILE_N, m), lambda b, i: (b, i, 0)),   # S row-block
        pl.BlockSpec((1, n, m), lambda b, i: (b, 0, 0)),        # full S
        pl.BlockSpec((TILE_N, n), lambda b, i: (i, 0)),         # Q row-block
        pl.BlockSpec((m, m), lambda b, i: (0, 0)),              # G
    ]
    out_specs = pl.BlockSpec((1, 1, 1), lambda b, i: (b, 0, 0))
    return grid, in_specs, out_specs


@functools.partial(jax.jit, static_argnames=("interpret",))
def edge_fitness_pallas(S: jax.Array, Q: jax.Array, G: jax.Array,
                        interpret: bool = False) -> jax.Array:
    """S: (B, n, m) f32 row-stochastic; Q: (n, n); G: (m, m). -> (B,) f32.

    n, m must be multiples of 128 (ops.py pads); padding rows of S and
    rows/cols of Q/G must be zero, which keeps the residual exact.
    """
    B, n, m = S.shape
    grid, in_specs, out_specs = _grid_specs(B, n, m, S.dtype, Q.dtype)
    out = pl.pallas_call(
        _fitness_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(S, S, Q, G)
    return out.reshape(B)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def edge_fitness_quantized_pallas(S_q: jax.Array, Q: jax.Array, G: jax.Array,
                                  scale: int = 255,
                                  interpret: bool = False) -> jax.Array:
    """Fixed-point fitness. S_q: (B, n, m) uint8; Q/G: {0,1}. -> (B,) f32."""
    B, n, m = S_q.shape
    grid, in_specs, out_specs = _grid_specs(B, n, m, S_q.dtype, Q.dtype)
    kernel = functools.partial(_fitness_kernel_quantized, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(S_q, S_q, Q, G)
    return out.reshape(B)
