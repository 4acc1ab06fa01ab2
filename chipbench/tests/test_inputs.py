"""The benchmark's inputs: seeded traffic, listed buckets, frozen window
sets.

    python -m pytest chipbench/tests
"""
import copy
import glob
import json
import os

import numpy as np
import pytest

from chipbench import freeze_windows, generator, harness

SPEC = json.load(open(harness.SPEC_FILE))
CELLS = [w["name"] for w in SPEC["workloads"]]
SEEDS = (0, 7, 2**31 + 12345, 3 * 2**40 + 1)
TEST_WINDOWS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "data", "windows")
#: Every frozen window set: the benchmark's own and the tests' own.
WINDOW_SETS = sorted(glob.glob(os.path.join(freeze_windows.WINDOWS_DIR,
                                            "*.json"))
                     + glob.glob(os.path.join(TEST_WINDOWS_DIR, "*.json")))


def _warm_zipf():
    """The cell that ``traffic/warm_zipf.json`` is kept for, as a later
    change would add it: its own rate and buckets, cell 1's config."""
    cell = harness.load_cell("cloud.cold_poisson")
    cell.name = "cloud.warm_zipf"
    cell.traffic = harness._read_json(
        os.path.join(harness.HERE, "traffic", "warm_zipf.json"))
    cell.params = {"rate_hz": 200.0, "limits": {"missed_mappings_pct": 5.0},
                   "buckets": [[8, 128], [8, 64], [24, 144], [24, 80]]}
    cell.entry = dict(cell.entry, name=cell.name, traffic="warm_zipf",
                      why="Zipf repeats; buckets 8x128 8x64 24x144 24x80")
    return cell


def _cell(name):
    return _warm_zipf() if name == "cloud.warm_zipf" \
        else harness.load_cell(name)


MIXES = CELLS + ["cloud.warm_zipf"]


def _draws(cell, seed, stream=harness.STREAM_WINDOW, seconds=None):
    return generator.draw_requests(
        cell.traffic, cell.platform, cell.params["rate_hz"],
        seconds or SPEC["run_seconds"], seed, stream)


def _key(reqs):
    return [(r.due, r.window, r.family, r.burst, r.free.tobytes())
            for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    cell = _cell(name)
    for seed in SEEDS:
        assert _key(_draws(cell, seed)) == _key(_draws(cell, seed))
    assert _key(_draws(cell, 1)) != _key(_draws(cell, 2))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_work_in_another_order(name):
    """Seeds change the order, never the amount or the kinds of work:
    the same count, gaps, windows, families and free counts."""
    cell = _cell(name)
    ref = None
    for seed in SEEDS:
        reqs = _draws(cell, seed)
        gaps = np.diff([0.0] + [r.due for r in reqs])
        shape = (len(reqs),
                 sorted(np.round(gaps[gaps > 0], 9).tolist()),
                 sorted((r.window, r.family, int(r.free.sum()), r.burst)
                        for r in reqs))
        if ref is None:
            ref, dues = shape, [r.due for r in reqs]
        assert shape == ref
        # the same instants for every seed
        assert [r.due for r in reqs] == dues
        assert reqs[-1].due < SPEC["run_seconds"]


@pytest.mark.parametrize("name", MIXES)
def test_traffic_stays_in_listed_buckets(name):
    cell = _cell(name)
    windows = cell.windows
    seen = set()
    svc = cell.config["service"]
    for seed in SEEDS:
        for stream, seconds in ((harness.STREAM_WINDOW, None),
                                (harness.STREAM_WARM,
                                 cell.traffic["warm_pool"]),
                                (harness.STREAM_PREROLL,
                                 cell.traffic["preroll"])):
            reqs = generator.draw_requests(
                cell.traffic, cell.platform,
                cell.params["rate_hz"] if seconds is None else 1.0,
                seconds or SPEC["run_seconds"], seed, stream)
            harness.assert_buckets(cell, windows, reqs)
            seen |= {harness.bucket_of(windows[r.window].n,
                                       int(r.free.sum()), svc["n_multiple"],
                                       svc["m_multiple"]) for r in reqs}
    # every listed bucket is reached, and the entry's why names them
    assert seen == {tuple(b) for b in cell.params["buckets"]}
    for n, m in seen:
        assert f"{n}x{m}" in cell.entry["why"]


def test_traffic_outside_the_buckets_is_refused():
    cell = harness.load_cell("cloud.cold_poisson")
    windows = cell.windows
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["masks"]["extra_busy"] = 40
    with pytest.raises(harness.Refused):
        harness.assert_buckets(cell, windows, _draws(cell, 3))


def test_warm_pool_covers_every_class_at_every_batch_class():
    for name in MIXES:
        cell = _cell(name)
        top = max(cell.config["service"]["batch_classes"])
        for seed in SEEDS:
            reqs = generator.draw_requests(
                cell.traffic, cell.platform, 1.0, cell.traffic["warm_pool"],
                seed, harness.STREAM_WARM)
            window_classes = {(r.window, int(r.free.sum()))
                              for r in _draws(cell, seed)}
            counts = {}
            for r in reqs:
                k = (r.window, int(r.free.sum()))
                counts[k] = counts.get(k, 0) + 1
            assert window_classes <= set(counts)
            assert min(counts.values()) >= top


def test_masks_mark_exactly_the_family_base_and_four_more():
    cell = harness.load_cell("cloud.cold_poisson")
    rows, cols = cell.platform["noc_rows"], cell.platform["noc_cols"]
    for r in _draws(cell, 5):
        base = (np.ones(rows * cols, bool) if r.family == "full"
                else generator.half_base(r.family, rows, cols))
        assert not (r.free & ~base).any()
        assert int(base.sum() - r.free.sum()) == 4


def test_pool_requests_repeat_their_states():
    """Every request of a pool mix is one of the pool's states, or that
    state with one engine traded for a busy neighbour, in the mix's
    shares; the pool's windows and families are the same for each seed."""
    cell = _warm_zipf()
    layout = None
    for seed in SEEDS:
        pool = generator.pool_states(cell.traffic, cell.platform, seed)
        assert len(pool) == 64
        if layout is None:
            layout = [(s.window, s.family) for s in pool]
        assert [(s.window, s.family) for s in pool] == layout
        reqs = _draws(cell, seed)
        states = {s.free.tobytes(): s for s in pool}
        swapped = 0
        for r in reqs:
            if r.free.tobytes() in states:
                assert states[r.free.tobytes()].window == r.window
                continue
            swapped += 1
            near = [s for s in pool if s.window == r.window
                    and (s.free != r.free).sum() == 2
                    and s.free.sum() == r.free.sum()]
            assert near, "a request is neither a state nor one swap away"
        assert abs(swapped / len(reqs) - 0.25) < 0.01
        top = sum(r.free.tobytes() == pool[0].free.tobytes() for r in reqs)
        assert top > len(reqs) / 8      # rank 1 of 64 under Zipf 1.0


@pytest.mark.parametrize(
    "path", WINDOW_SETS,
    ids=[os.path.basename(p)[:-len(".json")] for p in WINDOW_SETS])
def test_frozen_windows_equal_the_lowering(path):
    """Each window of each set, lowered again from its recorded source,
    gives the set's file byte for byte: ``freeze_windows.py`` would
    write it unchanged."""
    with open(path) as f:
        text = f.read()
    assert freeze_windows.dumps(freeze_windows.refrozen(json.loads(text))) \
        == text


def test_platform_sets_are_the_zoo_workloads_from_stage_0():
    for platform in freeze_windows.PLATFORM_SETS:
        data = harness._read_json(os.path.join(freeze_windows.WINDOWS_DIR,
                                               platform + ".json"))
        assert data["platform"] == platform
        assert data["window_stages"] == 4
        for name, w in data["windows"].items():
            assert "source" not in w
            assert freeze_windows.source_of(name, w) == {
                "workload": name, "args": {}, "progress": 0}


def _spec_naming(tmp_path, config_name, set_name):
    """A copy of the benchmark's spec in which the configuration
    ``config_name`` names the window set ``set_name``."""
    spec = copy.deepcopy(SPEC)
    conf = next(c for c in spec["configs"] if c["name"] == config_name)
    config = harness._read_json(os.path.join(harness.ROOT, conf["file"]))
    config["windows"] = set_name
    conf["file"] = str(tmp_path / "config.json")
    (tmp_path / "config.json").write_text(json.dumps(config))
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    return str(tmp_path / "spec.json")


def test_a_configuration_is_served_from_the_set_it_names(tmp_path):
    spec_file = _spec_naming(tmp_path, "edge64.q8", "two_windows")
    cell = harness.load_cell("edge.burst_mixed", spec_file=spec_file,
                             windows_dir=TEST_WINDOWS_DIR)
    assert harness.window_set(cell.config) == "two_windows"
    frozen = harness._read_json(os.path.join(
        TEST_WINDOWS_DIR, "two_windows.json"))["windows"]
    assert set(cell.windows) == set(frozen) == {"resnet50.r112.s1",
                                                "mobilenetv2.s2"}
    for name, w in cell.windows.items():
        assert w.n == frozen[name]["n"]
        assert w.macs.tolist() == frozen[name]["macs"]
    # without the key, the set is the platform's
    plain = harness.load_cell("edge.burst_mixed")
    assert harness.window_set(plain.config) == "edge"
    assert set(plain.windows) == set(harness._read_json(os.path.join(
        freeze_windows.WINDOWS_DIR, "edge.json"))["windows"])


def test_a_set_of_another_platform_is_refused(tmp_path):
    spec_file = _spec_naming(tmp_path, "cloud128.q8", "two_windows")
    with pytest.raises(harness.Refused, match="platform 'edge'"):
        harness.load_cell("cloud.cold_poisson", spec_file=spec_file,
                          windows_dir=TEST_WINDOWS_DIR)


def test_frozen_window_sizes():
    w = harness.load_windows("cloud", "cloud")
    assert {k: v.n for k, v in w.items()} == {
        "mobilenetv2": 4, "efficientnet": 4, "deepseek-7b": 5,
        "qwen-7b": 5, "llama3-8b-wl": 5, "resnet50": 6, "nasnet": 16,
        "pnasnet": 18, "unet": 19}
