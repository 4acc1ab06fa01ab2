"""Whole runs on the CPU at a tiny size, with the look for a chip skipped:
``correct`` holds on the sound path and fails under each fault a decision
cell can have (an answer altered where it is produced: a served mapping
with one tile moved onto another tile's engine; and a "found" claimed
where no mapping exists). Also: the run refuses without a TPU, with the
wrong kernel backend, with an unknown device kind, and in a directory
that holds only the benchmark's own files."""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
import types

import pytest

from chipbench import harness, roofline
from chipbench.metrics_common import swarm_problems

ROOT = harness.ROOT
#: Four repeated states of two small windows: answers from the stores.
POOL = {"kind": "zipf_pool", "names": ["mobilenetv2", "efficientnet"],
        "states": 4, "exponent": 1.0, "swap_frac": 0.25}


def tiny_cell(names=("mobilenetv2", "efficientnet")):
    """The edge cell with its traffic and swarm cut to what the CPU can
    serve in seconds (small windows, two batch classes); ``names`` are
    the windows served uniformly, or a pool mix's ``windows``."""
    cell = harness.load_cell("edge.burst_mixed")
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.params = dict(cell.params, rate_hz=6.0)
    cell.config["pso"].update(num_particles=8, epochs=2, inner_steps=3)
    cell.config["service"]["batch_classes"] = [1, 2]
    cell.traffic.update(warm_pool=20, preroll=8, preroll_rounds=2, fill=12)
    cell.config["service"].update(warm_capacity=8, sim_capacity=4)
    cell.traffic["windows"] = (dict(names) if isinstance(names, dict) else
                               {"kind": "uniform", "names": list(names)})
    return cell


def run_tiny(fault=None, seed=2**31 + 99, trace=0, cell=None, **kw):
    import jax
    cell = cell or tiny_cell(**kw)
    argv = ["--workload", cell.name, "--seed", str(seed), "--seconds",
            "1.5", "--trace", str(trace)]
    if fault:
        argv += ["--fault", fault]
    dev = {"devices": jax.devices(), "kind": "TPU v5 lite",
           "peak": roofline.peak_for("TPU v5 lite")}
    return harness.run_cell(cell, harness.parse_args(argv), dev,
                            time.perf_counter(), persist_dir=False)


@pytest.fixture(scope="module")
def sound():
    return run_tiny()


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    m = sound["metrics"]
    assert set(m) == {m["name"] for m in json.load(
        open(harness.SPEC_FILE))["end_to_end"]}
    assert m["mapped_pct"]["value"] == 100.0
    assert all(v["value"] > 0 for v in m.values())
    assert list(sound)[-1] == "checks"
    assert all(c["value"] == 0 for c in sound["checks"].values())
    assert sound["checks"]["missed_mappings_pct"]["limit"] > 0
    assert all(c["limit"] == 0 for k, c in sound["checks"].items()
               if k != "missed_mappings_pct")


@pytest.mark.parametrize("fault,names,check", [
    ("alter_answer", ("mobilenetv2", "efficientnet"), "invalid_mappings"),
    # nasnet's window has odd cycles: the mesh holds no mapping of it
    ("claim_found", ("nasnet",), "found_without_mapping"),
    ("drop_half", ("mobilenetv2", "efficientnet"), "missed_mappings_pct"),
    # repeated states, answered from the Tier-0/1 stores
    ("alter_answer", POOL, "invalid_mappings"),
    ("drop_half", POOL, "missed_mappings_pct"),
])
def test_fault_makes_the_run_incorrect(fault, names, check):
    res = run_tiny(fault=fault, names=names)
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]


def test_traced_run_reports_the_layer_metrics_it_can_read():
    res = run_tiny(trace=1)
    names = set(res["metrics"])
    # on the CPU there is no device plane: the trace metrics stay silent
    assert {"gen_late_ms_p95", "fe_wait_ms", "host_sync_ms_per_drain",
            "window_compiles", "tier2_share_pct"} <= names
    assert not names & {"device_idle_pct", "epoch_fused_roofline",
                        "epoch_finish_roofline"}
    assert res["correct"] is True


def test_a_mix_of_repeated_states_is_served_from_its_pool(capfd):
    """A ``zipf_pool`` mix needs no code of its own: set-up serves each
    pool state once, and the window's repeats are answered correctly."""
    res = run_tiny(names=POOL)
    assert res["correct"] is True and res["failed"] == 0
    assert "pool: 4 states" in capfd.readouterr().err


def test_a_run_serves_the_window_set_of_its_cell():
    """A run serves the windows of the cell's window set, here one
    lowered from stages other than 0, and is correct."""
    cell = tiny_cell()
    cell.windows = harness.load_windows(
        "two_windows", cell.platform["name"],
        os.path.join(os.path.dirname(__file__), "data", "windows"))
    cell.traffic["windows"] = {"kind": "uniform", "names": sorted(
        cell.windows)}
    res = run_tiny(cell=cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"]["mapped_pct"]["value"] == 100.0


def test_roofline_count_ignores_padding():
    """The same problems served under two bucketings (rows to 8 and lanes
    to 16, or rows to 32 and lanes to 128) give the same count."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.accel.platform import get_platform
    from repro.accel.target_graph import free_engine_graph
    from repro.core.graphs import Graph
    from repro.core.pso import PSOConfig
    from repro.core.service import MatcherService
    windows = harness.load_windows("edge", "edge")
    w = windows["resnet50"]
    free = [True] * 64
    free[5] = free[17] = False
    query = Graph(adj=w.adj, types=w.types, weights=w.macs)
    target = free_engine_graph(get_platform("edge"), free)
    cfg = PSOConfig(num_particles=8, epochs=2, inner_steps=3, quantized=True)
    counts = []
    import numpy as np
    for n_mult, m_mult in ((8, 16), (32, 128)):
        svc = MatcherService(cfg, n_multiple=n_mult, m_multiple=m_mult,
                             persist_dir=False)
        svc.submit(query, target, key=jax.random.PRNGKey(3))
        res = svc.drain()[0]
        assert res.bucket[0] % n_mult == 0 and res.bucket[1] % m_mult == 0
        rec = types.SimpleNamespace(
            result=res, done=0.0,
            problem=types.SimpleNamespace(req=types.SimpleNamespace(
                window="resnet50", free=np.asarray(free))))
        ctx = types.SimpleNamespace(records=[rec], end=1.0,
                                    windows=windows)
        probs = swarm_problems(ctx)
        assert probs == [(6, 62, res.epochs_run)]
        counts.append(roofline.swarm_least_times(
            probs, dict(num_particles=8, inner_steps=3, quantized=True,
                        refine_iters=6), roofline.peak_for("TPU v5 lite")))
    assert counts[0] == counts[1]


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        roofline.peak_for("TPU v99")
    assert roofline.peak_for("TPU v5 lite")["int8_ops_per_s"] == 393e12


class _FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,backend,why", [
    ("TPU v5 lite", "interpret", "backend"),
    ("TPU v99", "pallas", "TPU v99"),
])
def test_check_device_refuses(monkeypatch, kind, backend, why):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(kind)])
    if backend:
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    cell = harness.load_cell("cloud.cold_poisson")
    with pytest.raises(harness.Refused, match=why):
        harness.check_device(cell)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "cloud.cold_poisson", "--seed", "5", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
    json.load(open(tmp_path / "BENCHMARK.json"))
