"""The served Pallas kernels, and the Tier-2 program around them, compile
for a TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology, so Mosaic's refusals (unsupported casts or
matmuls, misaligned blocks, too much VMEM) fail here instead of on the
chip. Shapes are the cloud deployment's: 64 particles, a 19-tile window
bucketed to 24 rows, a free 128-engine array bucketed to 144 columns
(room for the dummy rows' engines; the kernels pad it to 256 lanes), 12
inner steps; one problem and a batch of 8. The topology is described
inside a fixture (never while a module is imported), and JAX's
persistent compilation cache is off around the compiles, because a
compile for a described device cannot be read back from it.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import pso
from repro.kernels import ops

N, ROWS, COLS, STEPS = 64, 24, 144, 12
u8, f32 = jnp.uint8, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; return the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text       # the Mosaic kernel is there
    return text


@pytest.mark.parametrize("P", [1, 8])
def test_prune_fixpoint_compiles_for_v5e(one_chip, P):
    _compile(lambda M, Q, G: ops.prune_fixpoint(M, Q, G, backend="pallas"),
             one_chip, ((P, ROWS, COLS), u8), ((P, ROWS, ROWS), u8),
             ((P, COLS, COLS), u8))


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("P", [1, 8])
def test_epoch_fused_compiles_for_v5e(one_chip, P, quantized):
    fn = functools.partial(ops.epoch_fused, omega=0.7, c1=1.4, c2=1.4,
                           c3=0.6, v_max=0.5, quantized=quantized,
                           backend="pallas")
    particles, plane = ((P, N, ROWS, COLS), f32), ((P, ROWS, COLS), f32)
    _compile(fn, one_chip, particles, particles, particles, ((P, N), f32),
             plane, ((P,), f32), plane, ((P, ROWS, COLS), u8),
             ((P, ROWS, ROWS), u8), ((P, COLS, COLS), u8),
             ((P, STEPS, N, 3), f32))


@pytest.mark.parametrize("P", [1, 8])
def test_epoch_finish_compiles_for_v5e(one_chip, P):
    fn = functools.partial(ops.epoch_finish, gumbel_tau=0.0,
                           refine_threshold=0.5, refine_iters=6,
                           elite_k=16, consensus_temp=25.0, backend="pallas")
    _compile(lambda S, f, mk, Q, G: fn(S, f, None, mk, Q, G), one_chip,
             ((P, N, ROWS, COLS), f32), ((P, N), f32),
             ((P, ROWS, COLS), u8), ((P, ROWS, ROWS), u8),
             ((P, COLS, COLS), u8))


def test_revalidate_program_compiles_for_v5e(one_chip):
    """The service's Tier-0/1 program for a batch of 8: pre-prune plus
    the fitness kernel, on the ``pallas`` suite."""
    cfg = pso.PSOConfig(quantized=True, backend="pallas", early_exit=True)
    B = 8
    carry = ((B, ROWS, COLS), f32), ((B,), f32), ((B, ROWS, COLS), f32)
    text = _compile(
        lambda Q, G, mk, S, f, C: pso._revalidate_batch_body(
            Q, G, mk, cfg, (S, f, C)),
        one_chip, ((B, ROWS, ROWS), u8), ((B, COLS, COLS), u8),
        ((B, ROWS, COLS), u8), *carry)
    assert text.count("tpu_custom_call") >= 2


def test_served_swarm_program_compiles_for_v5e(one_chip):
    """The service's Tier-2 program for a batch of 8, as
    ``MatcherService`` builds it: the swarm plus the on-device selection
    of each problem's served mapping, with no particle trace among its
    outputs."""
    cfg = pso.PSOConfig(quantized=True, backend="pallas", early_exit=True)
    B = 8
    key = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), B))

    def program(keys, Q, G, mk, S, f, C):
        return pso.served_outs(pso._match_batch_body(keys, Q, G, mk, cfg,
                                                     (S, f, C)))

    carry = ((B, ROWS, COLS), f32), ((B,), f32), ((B, ROWS, COLS), f32)
    shapes = (((key.shape, key.dtype),) + (((B, ROWS, ROWS), u8),
              ((B, COLS, COLS), u8), ((B, ROWS, COLS), u8)) + carry)
    _compile(program, one_chip, *shapes)
    outs = jax.eval_shape(program, *(jax.ShapeDtypeStruct(s, d)
                                     for s, d in shapes))
    assert outs["mapping"].shape == (B, ROWS, COLS)
    assert "mappings" not in outs
