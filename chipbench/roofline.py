"""Analytic work and bytes of the swarm's two fused kernels, and the least
time the chip could take for them.

The counts are of the algorithm (paper Algorithm 1, §3.4 quantized
scheme), at the problem's real size: ``n`` tiles of the window and ``m``
free engines, ``N`` particles, ``K`` inner steps per epoch, for the
epochs the problem actually ran. Padded rows and lanes, padded batch
slots, byte-split partial products and bf16 stand-ins for 8-bit
operands are not counted: a program that removes them shows a higher
share of the same work, not a smaller count. Contractions are
multiply-adds (2 operations each); elementwise and compare work counts
one operation per element. Operands are counted at the precision the
configuration states: 8-bit for the quantized swarm state and the 0/1
graph planes, float32 for per-particle scalars.

Adapted from ``benchmarks/roofline.py`` (``fitness_flops``,
``pso_update_flops``, ``requantize_flops``, ``epoch_hbm_bytes``,
``tail_hbm_bytes``), with the state counted at 8 bits where the
configuration is quantized and with the epilogue's work counted too.
"""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS_FILE = os.path.join(HERE, "peaks.json")


def load_peaks() -> Dict[str, Dict]:
    with open(PEAKS_FILE) as f:
        return json.load(f)["devices"]


def peak_for(device_kind: str) -> Dict:
    """Peak table entry of one ``device_kind``; an unknown kind is an
    error, never a default."""
    peaks = load_peaks()
    if device_kind not in peaks:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"{PEAKS_FILE}; known: {sorted(peaks)}")
    return peaks[device_kind]


def _contract(a: int, b: int, c: int) -> float:
    """Operations of an (a, b) x (b, c) contraction."""
    return 2.0 * a * b * c


def fitness_ops(n: int, m: int) -> float:
    """Edge-consistency fitness -||Q - S G S^T||^2 for one particle."""
    return _contract(n, m, m) + _contract(n, m, n) + 3.0 * n * n


def epoch_fused_ops(n: int, m: int, particles: int, steps: int,
                    quantized: bool) -> float:
    """One epoch of the fused inner loop: per particle and step the
    velocity/position update with row normalisation (about 16 operations
    per element of S), the requantisation when quantized (about 10), the
    fitness, and the local/global best update."""
    per = fitness_ops(n, m) + 16.0 * n * m + (10.0 * n * m if quantized
                                             else 0.0) + 2.0 * n * m
    return steps * particles * per


def epoch_fused_bytes(n: int, m: int, particles: int, steps: int,
                      quantized: bool) -> float:
    """HBM bytes of one fused-epoch launch per problem: the particle state
    (S, V, S_local) read once and S written back, the controller planes
    (S*, S-bar, mask) and graph planes (Q, G) read once, the pre-drawn
    randoms, and the fitness trace."""
    s_bytes = 1 if quantized else 4
    state = 3 * particles * n * m * s_bytes + 4 * particles
    planes = 3 * n * m * s_bytes + n * n + m * m
    randoms = 4 * steps * particles * 3
    out = particles * n * m * s_bytes + n * m * s_bytes + 4 * (steps + 2) \
        + 4 * particles
    return float(state + planes + randoms + out)


def epoch_finish_ops(n: int, m: int, particles: int,
                     refine_iters: int) -> float:
    """One epoch epilogue per problem: two structured projections (n
    placements, each an (n)x(n, m) support count and an (m, m)x(m)
    free-neighbour count), one greedy projection (n masked arg-maxes
    over n x m), ``refine_iters`` Ullmann sweeps (two (n, m)x(m, m) and
    two (n, n)x(n, m) contractions), two feasibility checks (M G M^T),
    and the merge."""
    structured = n * (_contract(1, n, m) + _contract(1, m, m) + 4.0 * m)
    greedy = n * 2.0 * n * m
    sweep = 2 * _contract(n, m, m) + 2 * _contract(n, n, m) + 3.0 * n * m
    feasible = _contract(n, m, m) + _contract(n, m, n) + 2.0 * n * m
    per = 2 * structured + greedy + refine_iters * sweep + 2 * feasible \
        + n * m
    return particles * per


def epoch_finish_bytes(n: int, m: int, particles: int,
                       quantized: bool) -> float:
    """HBM bytes of one epilogue launch per problem: the final swarm read
    once, the graph planes, the mapping planes and flags written."""
    s_bytes = 1 if quantized else 4
    return float(particles * n * m * s_bytes + n * m + n * n + m * m
                 + particles * n * m + 4 * particles)


def least_time_s(ops: float, nbytes: float, peak: Dict,
                 quantized: bool) -> float:
    """The larger of operations over the peak rate of the stated
    precision and bytes over the HBM bandwidth."""
    rate = peak["int8_ops_per_s"] if quantized else peak["f32_flops_per_s"]
    return max(ops / rate, nbytes / peak["hbm_bytes_per_s"])


def swarm_least_times(problems, pso: Dict, peak: Dict) -> Dict[str, float]:
    """Least device seconds of each fused kernel over ``problems``:
    ``(n, m, epochs_run)`` of every real problem a swarm launch served."""
    N, K = pso["num_particles"], pso["inner_steps"]
    q = bool(pso["quantized"])
    fused = finish = 0.0
    for n, m, epochs in problems:
        if epochs <= 0:
            continue
        fused += epochs * least_time_s(
            epoch_fused_ops(n, m, N, K, q), epoch_fused_bytes(n, m, N, K, q),
            peak, q)
        finish += epochs * least_time_s(
            epoch_finish_ops(n, m, N, pso["refine_iters"]),
            epoch_finish_bytes(n, m, N, q), peak, q)
    return {"epoch_fused": fused, "epoch_finish": finish}
