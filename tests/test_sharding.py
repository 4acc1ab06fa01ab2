"""Sharding-spec inference + distributed pieces that need >1 device
(run in subprocesses with fake CPU devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(__file__))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=900, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_param_spec_rules():
    script = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.runtime import sharding as shd

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    params = {
        "embed": jnp.zeros((1024, 64)),
        "blocks": {"attn": {"wq": jnp.zeros((8, 64, 8, 16)),
                            "wo": jnp.zeros((8, 8, 16, 64))},
                   "ffn": {"experts": {"gate": jnp.zeros((8, 4, 64, 32))},
                           "router": jnp.zeros((8, 64, 4))}},
        "final_ln": {"scale": jnp.zeros((64,))},
    }
    specs = shd.infer_param_specs(params, mesh)
    assert specs["embed"] == P("model", "data"), specs["embed"]
    # stacked leading layer dim stays unsharded
    assert specs["blocks"]["attn"]["wq"] == P(None, "data", "model", None)
    assert specs["blocks"]["attn"]["wo"] == P(None, "model", None, "data")
    assert specs["blocks"]["ffn"]["experts"]["gate"] == \\
        P(None, "model", "data", None)
    assert specs["blocks"]["ffn"]["router"] == P(None, "data", None)
    assert specs["final_ln"]["scale"] == P(None)
    print("SPEC-RULES-OK")
    """
    assert "SPEC-RULES-OK" in _run(script)


def test_divisibility_fallback():
    script = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.runtime import sharding as shd
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    # kv head dim 3 not divisible by model=2 → replicated
    params = {"wk": jnp.zeros((64, 3, 16))}
    specs = shd.infer_param_specs(params, mesh)
    assert specs["wk"] == P("data", None, None), specs["wk"]
    # batch 1 cache → sequence gets the data axis (context parallel)
    cache = {"k": jnp.zeros((4, 1, 64, 8, 16))}
    cspecs = shd.infer_cache_specs(cache, mesh)
    assert cspecs["k"][1] is None and cspecs["k"][2] == "data"
    print("FALLBACK-OK")
    """
    assert "FALLBACK-OK" in _run(script)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The FSDP+TP train step must be numerically identical to the
    unsharded one."""
    script = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.models import build_model
    from repro.runtime.train_loop import (make_train_state, make_train_step,
                                          state_specs)
    from repro.runtime import sharding as shd
    import sys
    sys.path.insert(0, "tests")
    from test_smoke_archs import reduce_config

    cfg = reduce_config(get_config("llama3-8b"))
    model = build_model(cfg)
    tcfg = TrainConfig(learning_rate=1e-3, microbatches=2, z_loss=0.0)
    state = make_train_state(model, tcfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
    }
    # single device reference
    step1 = jax.jit(make_train_step(model, tcfg, mesh=None))
    s1, m1 = step1(jax.tree.map(lambda x: x, state), batch)

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    sspecs = state_specs(state, mesh)
    bspecs = shd.infer_batch_specs(batch, mesh)
    step8 = jax.jit(make_train_step(model, tcfg, mesh),
                    in_shardings=(shd.named(sspecs, mesh),
                                  shd.named(bspecs, mesh)),
                    out_shardings=(shd.named(sspecs, mesh), None))
    s8, m8 = step8(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]),
                               rtol=2e-4)
    w1 = np.asarray(jax.tree.leaves(s1["params"])[0])
    w8 = np.asarray(jax.tree.leaves(s8["params"])[0])
    np.testing.assert_allclose(w1, w8, atol=3e-4)
    print("SHARDED-TRAIN-OK", float(m8["loss"]))
    """
    assert "SHARDED-TRAIN-OK" in _run(script)


@pytest.mark.slow
def test_grad_compression_semantics():
    """int8 error-feedback psum ≈ exact mean, and error feedback keeps the
    cumulative bias bounded over steps."""
    script = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.optim.grad_compress import compressed_psum

    mesh = jax.make_mesh((8,), ("data",))
    D = 8

    @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
             out_specs=(P("data"), P("data")), check_vma=False)
    def one_round(g, err):
        mean, new_err = compressed_psum(g[0], err[0], "data", D)
        return mean[None], new_err[None]

    key = jax.random.PRNGKey(0)
    gs = jax.random.normal(key, (D, 256))
    errs = jnp.zeros((D, 256))
    exact = gs.mean(0)
    # accumulate compressed means over rounds; error feedback must keep
    # the time-averaged estimate close to the true mean
    acc = jnp.zeros((256,))
    rounds = 8
    for _ in range(rounds):
        mean, errs = one_round(gs, errs)
        acc = acc + mean[0]
    est = acc / rounds
    err_1shot = float(jnp.abs(mean[0] - exact).max())
    err_avg = float(jnp.abs(est - exact).max())
    assert err_avg < err_1shot or err_avg < 2e-3, (err_avg, err_1shot)
    assert err_avg < 0.05
    print("COMPRESS-OK", err_1shot, err_avg)
    """
    assert "COMPRESS-OK" in _run(script)
