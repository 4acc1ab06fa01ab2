#!/usr/bin/env python3
"""Freeze the query windows the benchmark serves into ``data/windows/``.

    JAX_PLATFORMS=cpu python3 chipbench/freeze_windows.py [--set NAME ...]

A window set is one file, ``data/windows/<set>.json``: the platform it
was lowered for, the window length in stages, and its windows as plain
data (edge list, tile types, tile MACs). Each window records how it was
lowered under ``"source"``: the zoo workload, the keyword arguments of
its builder, and the stage the window starts at. A window without the
key is its own name's workload, built with no arguments, from stage 0
(``DEFAULT_SOURCE``). Each is lowered as the program's
``build_preemptible_dag`` does for one engine's tile capacity.

Without ``--set`` the platform sets ``cloud`` and ``edge`` are written
again; ``--set NAME`` re-lowers that set from the sources its file
records. A new set starts as a file that holds only its platform, its
``window_stages`` and each window's ``source``. The benchmark reads only
the frozen files, so a later change to the lowering cannot change what
the benchmark sends; the CPU test ``tests/test_inputs.py`` says when the
two have drifted apart.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOWS_DIR = os.path.join(HERE, "data", "windows")
PLATFORM_SETS = ("cloud", "edge")


def default_source(name: str) -> dict:
    return {"workload": name, "args": {}, "progress": 0}


def source_of(name: str, window: dict) -> dict:
    return window.get("source", default_source(name))


def lowered_windows(platform_name: str, sources: dict,
                    window_stages: int) -> dict:
    """``{name: {"n", "edges", "types", "macs"[, "source"]}}`` as the
    program lowers each ``sources[name]`` today on the named platform; a
    source other than the default is kept with its window."""
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import numpy as np
    from repro.accel.platform import get_platform
    from repro.core.preemptible_dag import build_preemptible_dag
    from repro.workloads.zoo import WORKLOAD_ZOO

    plat = get_platform(platform_name)
    out = {}
    for name, src in sources.items():
        wg = WORKLOAD_ZOO[src["workload"]](**src["args"])
        g = build_preemptible_dag(
            [(0, wg, src["progress"])],
            tile_capacity_macs=plat.engine_tile_capacity_macs(),
            window_stages=window_stages).graph
        u, v = np.nonzero(np.asarray(g.adj))
        out[name] = {"n": int(g.n),
                     "edges": [[int(a), int(b)] for a, b in zip(u, v)],
                     "types": [int(t) for t in np.asarray(g.types)],
                     "macs": [float(w) for w in np.asarray(g.weights)]}
        if src != default_source(name):
            out[name]["source"] = src
    return out


def refrozen(data: dict) -> dict:
    """A set's file contents with every window lowered again from its
    recorded source."""
    sources = {name: source_of(name, w)
               for name, w in data["windows"].items()}
    return {"platform": data["platform"],
            "window_stages": data["window_stages"],
            "windows": lowered_windows(data["platform"], sources,
                                       data["window_stages"])}


def dumps(data: dict) -> str:
    return json.dumps(data, indent=1, sort_keys=True) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/freeze_windows.py")
    ap.add_argument("--set", dest="sets", action="append",
                    help="a window set to lower again (repeatable); "
                         "default: " + " ".join(PLATFORM_SETS))
    args = ap.parse_args(argv)
    for name in args.sets or PLATFORM_SETS:
        path = os.path.join(WINDOWS_DIR, f"{name}.json")
        with open(path) as f:
            text = dumps(refrozen(json.load(f)))
        with open(path, "w") as f:
            f.write(text)
        print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
