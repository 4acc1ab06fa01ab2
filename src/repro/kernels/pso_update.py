"""Pallas TPU kernel: fused PSO velocity/position/mask/row-normalize step.

Paper Algorithm 1 lines 8–11 touch five (n, m) matrices per particle per
inner step. Unfused, each op is a separate HBM round-trip (the step is
purely elementwise + a row reduction, i.e. VPU/memory-bound). This kernel
fuses the whole update so every matrix is read once and written once —
the TPU analogue of the paper's "arbiters and selectors added to existing
PEs to enable different [element-wise] operations" on one pass through the
array.

Division-free normalization: rows are rescaled by a computed reciprocal
(one divide per row of a (TILE_N, 1) vector, amortized over m lanes),
mirroring the paper's reconfigurable-reciprocal multiplier.

Tiling: grid = (B, n/TILE_N). Blocks are (TILE_N, m) so a full row lives in
one block and the row-sum is local. Per-particle PSO randoms r ∈ R³ ride in
SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


TILE_N = 128
EPS = 1e-9


def _pso_update_kernel(r_ref, s_ref, v_ref, sl_ref, ss_ref, sb_ref, mask_ref,
                       s_out_ref, v_out_ref, *, omega, c1, c2, c3, v_max):
    s = s_ref[0].astype(jnp.float32)          # (TILE_N, m)
    v = v_ref[0].astype(jnp.float32)
    s_local = sl_ref[0].astype(jnp.float32)
    s_star = ss_ref[...].astype(jnp.float32)  # shared across particles
    s_bar = sb_ref[...].astype(jnp.float32)
    maskf = mask_ref[...].astype(jnp.float32)

    r0 = r_ref[0, 0]
    r1 = r_ref[0, 1]
    r2 = r_ref[0, 2]

    v_new = (omega * v
             + c1 * r0 * (s_local - s)
             + c2 * r1 * (s_star - s)
             + c3 * r2 * (s_bar - s))
    v_new = jnp.clip(v_new, -v_max, v_max)
    s_new = jnp.maximum(s + v_new, 0.0) * maskf

    row_sum = jnp.sum(s_new, axis=1, keepdims=True)            # (TILE_N, 1)
    inv = 1.0 / jnp.maximum(row_sum, EPS)                      # reciprocal
    mask_rows = jnp.sum(maskf, axis=1, keepdims=True)
    uniform = maskf * (1.0 / jnp.maximum(mask_rows, 1.0))
    s_new = jnp.where(row_sum > EPS, s_new * inv, uniform)

    s_out_ref[0] = s_new.astype(s_out_ref.dtype)
    v_out_ref[0] = v_new.astype(v_out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("omega", "c1", "c2", "c3", "v_max", "interpret"))
def pso_update_pallas(S, V, S_local, S_star, S_bar, mask, r,
                      omega: float, c1: float, c2: float, c3: float,
                      v_max: float = 1.0, interpret: bool = False):
    """Batched fused PSO step.

    S, V, S_local: (B, n, m) f32 per-particle state.
    S_star, S_bar, mask: (n, m) shared.
    r: (B, 8) f32 per-particle randoms (slots 0..2 used; padded for SMEM
       lane alignment).
    Returns (S_new, V_new).
    """
    B, n, m = S.shape
    n_tiles = pl.cdiv(n, TILE_N)
    kernel = functools.partial(_pso_update_kernel, omega=omega, c1=c1, c2=c2,
                               c3=c3, v_max=v_max)
    blk3 = lambda b, i: (b, i, 0)
    shared = lambda b, i: (i, 0)
    s_new, v_new = pl.pallas_call(
        kernel,
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 8), lambda b, i: (b, 0),
                         memory_space=pltpu.SMEM),               # r
            pl.BlockSpec((1, TILE_N, m), blk3),                  # S
            pl.BlockSpec((1, TILE_N, m), blk3),                  # V
            pl.BlockSpec((1, TILE_N, m), blk3),                  # S_local
            pl.BlockSpec((TILE_N, m), shared),                   # S*
            pl.BlockSpec((TILE_N, m), shared),                   # S̄
            pl.BlockSpec((TILE_N, m), shared),                   # mask
        ],
        out_specs=[
            pl.BlockSpec((1, TILE_N, m), blk3),
            pl.BlockSpec((1, TILE_N, m), blk3),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, n, m), jnp.float32),
            jax.ShapeDtypeStruct((B, n, m), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(r, S, V, S_local, S_star, S_bar, mask)
    return s_new, v_new
