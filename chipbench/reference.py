"""The plain reference: what a served decision must say, from first
principles, with nothing of the program imported.

A decision request is one query window (tiles, typed, with directed
edges) and the set of engines free on a 2-D mesh NoC. A served mapping
is valid when every tile sits on exactly one free engine its type may
use, no engine holds two tiles, and every window edge lands on a mesh
link. ``check_mapping`` tests one served mapping (a copy of the numpy
check ``chip_smoke.py`` runs, restated against the NoC itself instead
of the program's graph objects); ``mapping_exists`` decides by itself,
independently of the swarm, whether any valid mapping exists.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

# Tile type -> engine type compatibility (tile types: 0 MAC, 1 vector,
# 2 reduce, 3 any). Every engine of the paper's platforms is a MAC-array
# engine with the vector and comparator extensions, so every tile type
# runs on it.
ENGINE_TYPE = 0
_COMPAT = np.zeros((4, 4), np.uint8)
_COMPAT[0, 0] = _COMPAT[1, 0] = _COMPAT[1, 1] = 1
_COMPAT[2, 2] = _COMPAT[2, 0] = 1
_COMPAT[3, :] = 1


class Window:
    """One frozen query window: ``n`` tiles, directed ``edges``, ``types``."""

    def __init__(self, name: str, n: int, edges: Sequence[Sequence[int]],
                 types: Sequence[int], macs: Sequence[float]):
        self.name = name
        self.n = int(n)
        self.adj = np.zeros((self.n, self.n), np.uint8)
        for a, b in edges:
            self.adj[a, b] = 1
        self.types = np.asarray(types, np.int32)
        self.macs = np.asarray(macs, np.float32)

    def undirected(self) -> np.ndarray:
        return (self.adj | self.adj.T) != 0


def mesh_adjacency(rows: int, cols: int) -> np.ndarray:
    """(E, E) bool: engines r*cols+c joined by their 4-neighbour links."""
    e = rows * cols
    a = np.zeros((e, e), bool)
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            if c + 1 < cols:
                a[i, i + 1] = a[i + 1, i] = True
            if r + 1 < rows:
                a[i, i + cols] = a[i + cols, i] = True
    return a


class InvalidMapping(Exception):
    """A served mapping breaks one of the guarantees; the message says
    which."""


def check_mapping(engine_of: np.ndarray, window: Window, free: np.ndarray,
                  mesh: np.ndarray) -> None:
    """Raise :class:`InvalidMapping` unless ``engine_of[i]`` (the engine
    id tile i was served on) is a valid mapping of ``window`` onto the
    ``free`` engines of the mesh."""
    engine_of = np.asarray(engine_of, np.int64)
    if engine_of.shape != (window.n,):
        raise InvalidMapping(f"{engine_of.shape[0]} tiles mapped, "
                             f"window has {window.n}")
    if (engine_of < 0).any() or (engine_of >= free.shape[0]).any():
        raise InvalidMapping("a tile is not mapped to exactly one engine")
    if len(set(engine_of.tolist())) != window.n:
        raise InvalidMapping("an engine is used twice")
    if not free[engine_of].all():
        raise InvalidMapping("a tile sits on a busy engine")
    if not _COMPAT[window.types, ENGINE_TYPE].all():
        raise InvalidMapping("a tile sits on an engine of the wrong type")
    u, v = np.nonzero(window.adj)
    if not mesh[engine_of[u], engine_of[v]].all():
        raise InvalidMapping("a window edge is not covered by a NoC link")


def engines_from_matrix(M: np.ndarray, free: np.ndarray) -> np.ndarray:
    """Engine id of each tile from a served (n, free-engine) 0/1 matrix
    whose columns are the free engines in ascending id order. Raises
    :class:`InvalidMapping` when a row holds other than one 1."""
    M = np.asarray(M)
    idx = np.flatnonzero(free)
    if M.ndim != 2 or M.shape[1] != idx.shape[0]:
        raise InvalidMapping(f"mapping shape {M.shape} for "
                             f"{idx.shape[0]} free engines")
    if not (M.sum(axis=1) == 1).all():
        raise InvalidMapping("a tile is not mapped to exactly one engine")
    return idx[M.argmax(axis=1)]


def is_bipartite(und: np.ndarray) -> bool:
    n = und.shape[0]
    side = -np.ones(n, np.int64)
    for s in range(n):
        if side[s] >= 0:
            continue
        side[s] = 0
        stack = [s]
        while stack:
            u = stack.pop()
            for v in np.flatnonzero(und[u]):
                if side[v] < 0:
                    side[v] = 1 - side[u]
                    stack.append(v)
                elif side[v] == side[u]:
                    return False
    return True


def mapping_exists(window: Window, free: np.ndarray, mesh: np.ndarray,
                   budget: int = 2_000_000) -> Optional[bool]:
    """Whether ``window`` has any valid mapping onto the ``free`` engines.

    The NoC mesh is bipartite, so a window with an odd cycle has none.
    Otherwise a depth-first search places tiles in breadth-first order,
    each next to the engines of its already-placed neighbours. Returns
    None only when ``budget`` placements did not settle it."""
    n = window.n
    if not _COMPAT[window.types, ENGINE_TYPE].all():
        return False
    if n > int(free.sum()):
        return False
    und = window.undirected()
    if not is_bipartite(und):
        return False
    free_idx = np.flatnonzero(free)
    nbrs = [np.flatnonzero(mesh[e] & free) for e in range(free.shape[0])]
    # placement order: breadth-first within each component
    order, seen = [], np.zeros(n, bool)
    for s in range(n):
        if seen[s]:
            continue
        seen[s] = True
        queue = [s]
        while queue:
            u = queue.pop(0)
            order.append(u)
            for v in np.flatnonzero(und[u]):
                if not seen[v]:
                    seen[v] = True
                    queue.append(v)
    placed_nbrs = [[v for v in np.flatnonzero(und[u])
                    if order.index(v) < order.index(u)] for u in order]
    img = -np.ones(n, np.int64)
    used = np.zeros(free.shape[0], bool)
    steps = [0]

    def candidates(k):
        u = order[k]
        pn = placed_nbrs[k]
        if not pn:
            return [e for e in free_idx if not used[e]]
        cand = set(nbrs[img[pn[0]]].tolist())
        for v in pn[1:]:
            cand &= set(nbrs[img[v]].tolist())
        return [e for e in sorted(cand) if not used[e]]

    def place(k) -> Optional[bool]:
        if k == n:
            return True
        for e in candidates(k):
            steps[0] += 1
            if steps[0] > budget:
                return None
            img[order[k]] = e
            used[e] = True
            got = place(k + 1)
            used[e] = False
            if got is None or got:
                return got
        img[order[k]] = -1
        return False

    return place(0)
