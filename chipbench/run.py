#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, (traced runs)
``breakdown``, and last ``checks``, the numbers compared with their
limits. With ``--trace 0`` the metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics. The run exits non-zero, and
prints no result, when JAX finds no TPU or fewer chips than the cell
asks for, when the kernel backend does not resolve to ``pallas``, or
when the device kind is not in ``peaks.json``. See ``harness.py``.
"""
import time

_T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=_T_PROCESS))
