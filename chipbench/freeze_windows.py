#!/usr/bin/env python3
"""Freeze the query windows the benchmark serves into ``data/windows/``.

    JAX_PLATFORMS=cpu python3 chipbench/freeze_windows.py

Each zoo workload's first 4-stage preemptible window, as the program's
``build_preemptible_dag`` lowers it for one engine's tile capacity, is
written once per platform as plain data (edge list, tile types, tile
MACs). The benchmark reads only the frozen files, so a later change to
the lowering cannot change what the benchmark sends; the CPU test
``tests/test_inputs.py`` says when the two have drifted apart.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOWS_DIR = os.path.join(HERE, "data", "windows")
WORKLOADS = ("mobilenetv2", "resnet50", "unet", "efficientnet", "nasnet",
             "pnasnet", "deepseek-7b", "qwen-7b", "llama3-8b-wl")
WINDOW_STAGES = 4


def lowered_windows(platform_name: str) -> dict:
    """``{workload: {"n", "edges", "types", "macs"}}`` as the program
    lowers them today on the named platform."""
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import numpy as np
    from repro.accel.platform import get_platform
    from repro.core.preemptible_dag import build_preemptible_dag
    from repro.workloads.zoo import get_workload

    plat = get_platform(platform_name)
    out = {}
    for name in WORKLOADS:
        g = build_preemptible_dag(
            [(0, get_workload(name), 0)],
            tile_capacity_macs=plat.engine_tile_capacity_macs(),
            window_stages=WINDOW_STAGES).graph
        u, v = np.nonzero(np.asarray(g.adj))
        out[name] = {"n": int(g.n),
                     "edges": [[int(a), int(b)] for a, b in zip(u, v)],
                     "types": [int(t) for t in np.asarray(g.types)],
                     "macs": [float(w) for w in np.asarray(g.weights)]}
    return out


def main() -> int:
    os.makedirs(WINDOWS_DIR, exist_ok=True)
    for platform_name in ("cloud", "edge"):
        path = os.path.join(WINDOWS_DIR, f"{platform_name}.json")
        with open(path, "w") as f:
            json.dump({"platform": platform_name,
                       "window_stages": WINDOW_STAGES,
                       "windows": lowered_windows(platform_name)}, f,
                      indent=1, sort_keys=True)
            f.write("\n")
        print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
