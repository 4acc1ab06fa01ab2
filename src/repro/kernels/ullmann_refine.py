"""Pallas TPU kernel: one Ullmann refinement sweep, batched over particles.

The refinement is the feasibility-pruning workhorse of the matcher and is
"feasibility verification through matrix multiplication" (paper §3.3): all
four products below are {0,1}/small-int matmuls that map onto the MXU's
int8×int8→int32 path.

Per particle p with candidate matrix M (n, m):
    support_out = M @ G^T          # candidates of u adjacent *from* j
    support_in  = M @ G            # candidates of u adjacent *to* j
    viol        = Q @ [support_out == 0] + Q^T @ [support_in == 0]
    M'          = M ⊙ [viol == 0]

Tiling: grid = (B,); each step keeps one particle's full M plus Q and G in
VMEM. Scheduler-scale graphs (n, m ≤ 512 after padding) need
512·512·(1+1+1) int8 + int32 temporaries ≈ 4 MB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _refine_kernel(m_ref, q_ref, g_ref, o_ref):
    m_in = m_ref[0].astype(jnp.int32)                  # (n, m)
    q = q_ref[...].astype(jnp.int32)                   # (n, n)
    g = g_ref[...].astype(jnp.int32)                   # (m, m)

    support_out = jax.lax.dot_general(
        m_in, g, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)              # M @ G^T
    support_in = jnp.dot(m_in, g, preferred_element_type=jnp.int32)

    miss_out = (support_out == 0).astype(jnp.int32)
    miss_in = (support_in == 0).astype(jnp.int32)

    viol = (jnp.dot(q, miss_out, preferred_element_type=jnp.int32)
            + jax.lax.dot_general(
                q, miss_in, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32))     # Q^T @ miss_in

    o_ref[0] = (m_in * (viol == 0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ullmann_refine_step_pallas(M: jax.Array, Q: jax.Array, G: jax.Array,
                               interpret: bool = False) -> jax.Array:
    """M: (B, n, m) uint8 candidates; Q: (n, n); G: (m, m). -> (B, n, m).

    Padding requirements (ops.py enforces): padded entries of M must be 0,
    padded rows/cols of Q and G zero — the sweep is then exact w.r.t. the
    unpadded semantics (zero Q rows contribute no violations).
    """
    B, n, m = M.shape
    out = pl.pallas_call(
        _refine_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n, m), lambda b: (b, 0, 0)),
            pl.BlockSpec((n, n), lambda b: (0, 0)),
            pl.BlockSpec((m, m), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, m), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n, m), M.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(M, Q, G)
    return out
