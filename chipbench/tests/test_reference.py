"""The plain reference against the program's own numpy check of a served
mapping (``chip_smoke.check_mapping``) and against brute force."""
import itertools
import os
import sys

import numpy as np
import pytest

from chipbench import harness, reference

ROOT = harness.ROOT


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    chip_smoke = pytest.importorskip("chip_smoke")
    from repro.accel.platform import get_platform
    from repro.accel.target_graph import free_engine_graph
    from repro.core.graphs import Graph
    return chip_smoke, get_platform, free_engine_graph, Graph


def _smoke_verdict(smoke, window, free, engine_of, platform="cloud"):
    """chip_smoke.check_mapping on the same mapping, as the (n, free)
    matrix the service serves."""
    chip_smoke, get_platform, free_engine_graph, Graph = smoke
    target = free_engine_graph(get_platform(platform), free)
    query = Graph(adj=window.adj, types=window.types, weights=window.macs)
    col = {e: j for j, e in enumerate(np.flatnonzero(free))}
    M = np.zeros((window.n, target.n), np.uint8)
    for i, e in enumerate(engine_of):
        if e in col:
            M[i, col[e]] = 1
    try:
        chip_smoke.check_mapping(M, query, target)
        return True
    except chip_smoke.SmokeFailure:
        return False


def _ours(window, free, engine_of, mesh):
    try:
        reference.check_mapping(np.asarray(engine_of), window, free, mesh)
        return True
    except reference.InvalidMapping:
        return False


def _snake(rows, cols):
    out = []
    for r in range(rows):
        cs = range(cols) if r % 2 == 0 else range(cols - 1, -1, -1)
        out += [r * cols + c for c in cs]
    return out


def test_planted_cases_agree_with_check_mapping(smoke):
    windows = harness.load_windows("cloud", "cloud")
    mesh = reference.mesh_adjacency(8, 16)
    unet = windows["unet"]
    free = np.ones(128, bool)
    # unet's window is a directed path: lay it along a snake of the mesh
    order = []
    u = [i for i in range(unet.n) if unet.adj[:, i].sum() == 0][0]
    while True:
        order.append(u)
        nxt = np.flatnonzero(unet.adj[u])
        if not len(nxt):
            break
        u = int(nxt[0])
    assert len(order) == unet.n
    snake = _snake(8, 16)
    good = np.empty(unet.n, np.int64)
    good[order] = snake[:unet.n]
    cases = {"valid": good}
    twice = good.copy()
    twice[order[3]] = good[order[5]]
    cases["engine twice"] = twice
    gap = good.copy()
    gap[order[-1]] = 127                  # far corner, not next to its pred
    cases["edge off the mesh"] = gap
    busy_free = free.copy()
    busy_free[good[order[2]]] = False
    for name, eng in cases.items():
        assert _ours(unet, free, eng, mesh) == \
            _smoke_verdict(smoke, unet, free, eng), name
    assert _ours(unet, free, good, mesh)
    assert not _ours(unet, free, twice, mesh)
    assert not _ours(unet, free, gap, mesh)
    # a tile on a busy engine: the served matrix cannot even name it
    assert not _ours(unet, busy_free, good, mesh)
    assert not _smoke_verdict(smoke, unet, busy_free, good)


def test_engines_from_matrix_refuses_a_row_without_one_engine():
    free = np.ones(4, bool)
    M = np.array([[1, 0, 0, 0], [0, 0, 0, 0]], np.uint8)
    with pytest.raises(reference.InvalidMapping):
        reference.engines_from_matrix(M, free)
    M[1, 2] = 1
    assert reference.engines_from_matrix(M, free).tolist() == [0, 2]


def _brute_exists(window, free, mesh):
    idx = np.flatnonzero(free)
    for perm in itertools.permutations(idx, window.n):
        try:
            reference.check_mapping(np.asarray(perm), window, free, mesh)
            return True
        except reference.InvalidMapping:
            continue
    return False


@pytest.mark.parametrize("seed", range(6))
def test_mapping_exists_agrees_with_brute_force(seed):
    rng = np.random.default_rng(seed)
    mesh = reference.mesh_adjacency(3, 3)
    for _ in range(8):
        n = int(rng.integers(2, 5))
        edges = [(a, b) for a in range(n) for b in range(a + 1, n)
                 if rng.random() < 0.5]
        w = reference.Window("w", n, edges, [0] * n, [1.0] * n)
        free = rng.random(9) < 0.7
        if free.sum() < n:
            continue
        assert reference.mapping_exists(w, free, mesh) == \
            _brute_exists(w, free, mesh)


def test_odd_cycle_windows_have_no_mapping_and_paths_do():
    mesh = reference.mesh_adjacency(8, 16)
    windows = harness.load_windows("cloud", "cloud")
    free = np.ones(128, bool)
    for name in ("nasnet", "pnasnet"):
        assert not reference.is_bipartite(windows[name].undirected())
        assert reference.mapping_exists(windows[name], free, mesh) is False
    for name in ("unet", "mobilenetv2", "resnet50", "efficientnet",
                 "deepseek-7b"):
        assert reference.mapping_exists(windows[name], free, mesh) is True
