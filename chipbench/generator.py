"""The one traffic generator: draws a cell's decision requests from the
parameters of its traffic file and the run's seed.

Every seed gives the same amount and the same kinds of work, at the
same instants, in another order, so two seeds differ only as two orders
of one workload do:

* **arrivals** — ``poisson``: ``⌊rate_hz × seconds⌋`` requests whose
  gaps are the exponential distribution's quantiles at ``(k + ½) / N``,
  shuffled once; ``burst``: as many arrival events, of which exactly
  ``round(burst_frac × events)`` bring ``burst_size`` requests at one
  instant (the compound Poisson process of the program's scenario
  registry, stratified the same way). The instants come from a fixed
  seed of their own (``ARRIVAL_SEED``), not from the run's;
* **windows** — ``uniform`` over ``names``, or ``mixed_burst`` (the first
  ``round(hard_frac × burst_size)`` members of a burst from ``hard``,
  every other request from ``easy``), each list used in equal shares;
* **free-engine masks** — a family base (``full``: the whole array;
  ``half``: one half busy — left or right columns, top or bottom rows)
  with exactly ``extra_busy`` more engines marked busy, drawn from the
  seed. Family shares and half sides are stratified within each kind of
  request (window and burst flag), so the same kinds meet the same
  families on every seed;
* **a pool of repeated states** (windows ``zipf_pool``) — instead of a
  fresh window and mask per request, ``states`` fixed (window, mask)
  states: state k serves ``names[k mod len(names)]`` on a family fixed
  by its rank, and the seed draws only its extra busy engines. Requests
  pick states by Zipf popularity (``exponent``), each state exactly its
  share of the requests; within each state's requests a share
  ``swap_frac`` have one free engine trade places with a busy neighbour
  (engine id ± 1). :func:`pool_states` gives the pool, which set-up
  serves once.

The seed is any whole number (NumPy's ``SeedSequence`` takes it whole).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

import numpy as np

HALF_SIDES = ("left", "right", "top", "bottom")
#: The arrival instants, and the layout of a pool of states, are drawn
#: from this seed, never from the run's.
ARRIVAL_SEED = 20260411
#: The stream of a run's seed that draws a pool's masks (the same for the
#: window, the warm-up and the pre-roll, so they meet one pool).
STREAM_POOL = 1_000_000


@dataclasses.dataclass
class Request:
    index: int
    due: float                 # seconds after the window opens
    window: str                # frozen window name
    free: np.ndarray           # (engines,) bool
    family: str                # "full" or a half side
    burst: bool


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use of one seed."""
    return np.random.default_rng([int(seed) % (1 << 63), int(stream)])


def stratified(rng: np.random.Generator, labels: Sequence, count: int,
               shares: Sequence[float] = None) -> List:
    """``count`` labels in fixed shares (equal by default), shuffled: the
    multiset is the same for every seed; remainders go to the first
    labels."""
    k = len(labels)
    shares = [1.0 / k] * k if shares is None else list(shares)
    counts = [int(math.floor(s * count)) for s in shares]
    for j in range(count - sum(counts)):
        counts[j % k] += 1
    out = [lab for lab, c in zip(labels, counts) for _ in range(c)]
    rng.shuffle(out)
    return out


def exponential_gaps(rng: np.random.Generator, rate_hz: float,
                     count: int) -> np.ndarray:
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate_hz
    rng.shuffle(gaps)
    return gaps


def half_base(side: str, rows: int, cols: int) -> np.ndarray:
    r, c = np.divmod(np.arange(rows * cols), cols)
    busy = {"left": c < cols // 2, "right": c >= cols // 2,
            "top": r < rows // 2, "bottom": r >= rows // 2}[side]
    return ~busy


def family_labels(rng: np.random.Generator, masks: Dict,
                  groups: List) -> List[str]:
    """One family label (``full`` or a half side) per request. Family
    shares and half sides are stratified within each group of requests
    that share a group label."""
    fams = list(masks["families"])
    shares = [masks["families"][f] for f in fams]
    labels: List = [None] * len(groups)
    for g in sorted(set(groups)):
        idx = [k for k, x in enumerate(groups) if x == g]
        fam = stratified(rng, fams, len(idx), shares)
        sides = iter(stratified(rng, HALF_SIDES,
                                sum(f == "half" for f in fam)))
        for k, f in zip(idx, fam):
            labels[k] = "full" if f == "full" else next(sides)
    return labels


def family_mask(rng: np.random.Generator, label: str, extra_busy: int,
                platform: Dict) -> np.ndarray:
    """The family's base with ``extra_busy`` more engines marked busy."""
    rows, cols = platform["noc_rows"], platform["noc_cols"]
    free = (np.ones(rows * cols, bool) if label == "full"
            else half_base(label, rows, cols))
    busy = rng.choice(np.flatnonzero(free), size=extra_busy, replace=False)
    free = free.copy()
    free[busy] = False
    return free


def draw_masks(rng: np.random.Generator, masks: Dict, platform: Dict,
               groups: List) -> List:
    """One ``(family label, free mask)`` per request, the families
    stratified within each group (window and burst flag), so every seed
    serves each kind of request on the same mix of mask families."""
    labels = family_labels(rng, masks, groups)
    return [(label, family_mask(rng, label, masks["extra_busy"], platform))
            for label in labels]


def draw_arrivals(arrival: Dict, rate_hz: float, events: int, stream: int):
    """``[(time, count, burst)]`` of the window's arrival events: one
    fixed draw per (rate, count, stream), the same for every seed, so
    that seeds differ only in which request comes at which instant."""
    rng = rng_for(ARRIVAL_SEED, stream)
    gaps = exponential_gaps(rng, rate_hz, events)
    times = np.cumsum(gaps)
    if arrival["kind"] == "poisson":
        return [(float(t), 1, False) for t in times]
    if arrival["kind"] == "burst":
        n_burst = int(round(arrival["burst_frac"] * events))
        flags = stratified(rng, [True, False], events,
                           [n_burst / max(events, 1),
                            1 - n_burst / max(events, 1)])
        return [(float(t), arrival["burst_size"] if b else 1, b)
                for t, b in zip(times, flags)]
    raise ValueError(f"unknown arrival kind {arrival['kind']!r}")


def draw_windows(rng: np.random.Generator, windows: Dict, slots: List):
    """One window name per request slot ``(burst, member index)``."""
    if windows["kind"] == "uniform":
        return stratified(rng, windows["names"], len(slots))
    if windows["kind"] == "mixed_burst":
        n_hard = max(int(round(windows["hard_frac"]
                               * windows["burst_size"])), 1)
        # hard burst members, easy burst members, single arrivals: each
        # kind of slot gets its pool in equal shares
        kinds = [0 if b and i < n_hard else 1 if b else 2 for b, i in slots]
        pools = (windows["hard"], windows["easy"], windows["easy"])
        names = [iter(stratified(rng, pools[k], kinds.count(k)))
                 for k in range(3)]
        return [next(names[k]) for k in kinds]
    raise ValueError(f"unknown window kind {windows['kind']!r}")


def pool_states(traffic: Dict, platform: Dict, seed: int
                ) -> List[Request]:
    """The pool of a ``zipf_pool`` mix, in rank order (empty for every
    other mix). Which window and family each rank holds is the same for
    every seed; the seed draws the extra busy engines."""
    pool = traffic["windows"]
    if pool["kind"] != "zipf_pool":
        return []
    names = [pool["names"][k % len(pool["names"])]
             for k in range(pool["states"])]
    labels = family_labels(rng_for(ARRIVAL_SEED, STREAM_POOL),
                           traffic["masks"], names)
    rng = rng_for(seed, STREAM_POOL)
    return [Request(index=k, due=0.0, window=nm, family=label,
                    free=family_mask(rng, label,
                                     traffic["masks"]["extra_busy"],
                                     platform), burst=False)
            for k, (nm, label) in enumerate(zip(names, labels))]


def swap_one_engine(rng: np.random.Generator, free: np.ndarray
                    ) -> np.ndarray:
    """The mask with one free engine traded for a busy neighbour (engine
    id ± 1), drawn from ``rng``: the free count stays the same."""
    pairs = [(e, nb) for e in np.flatnonzero(free) for nb in (e - 1, e + 1)
             if 0 <= nb < free.shape[0] and not free[nb]]
    e, nb = pairs[int(rng.integers(len(pairs)))]
    free = free.copy()
    free[e], free[nb] = False, True
    return free


def draw_from_pool(rng: np.random.Generator, traffic: Dict, platform: Dict,
                   seed: int, count: int) -> List:
    """``(window, family label, free mask)`` of ``count`` requests drawn
    from the pool: each state gets its Zipf share of the requests, and
    each state's own share ``swap_frac`` of them swap one engine."""
    pool = traffic["windows"]
    states = pool_states(traffic, platform, seed)
    weights = (np.arange(len(states)) + 1.0) ** -float(pool["exponent"])
    ranks = stratified(rng, list(range(len(states))), count,
                       (weights / weights.sum()).tolist())
    swaps: Dict[int, List[bool]] = {}
    for k in sorted(set(ranks)):
        c = ranks.count(k)
        n_swap = int(round(pool["swap_frac"] * c))
        swaps[k] = stratified(rng, [True, False], c,
                              [n_swap / c, 1 - n_swap / c])
    out = []
    for k in ranks:
        st = states[k]
        free = (swap_one_engine(rng, st.free) if swaps[k].pop()
                else st.free)
        out.append((st.window, st.family, free))
    return out


def draw_requests(traffic: Dict, platform: Dict, rate_hz: float,
                  seconds: float, seed: int, stream: int = 0
                  ) -> List[Request]:
    """The requests of one window of ``seconds`` at ``rate_hz`` arrival
    events per second. ``stream`` separates independent uses of one
    seed (the window, the pre-roll, the warm-up)."""
    rng = rng_for(seed, stream)
    events = max(int(math.floor(rate_hz * seconds)), 1)
    arrivals = draw_arrivals(traffic["arrival"], rate_hz, events, stream)
    slots = [(b, i) for _, count, b in arrivals for i in range(count)]
    if traffic["windows"]["kind"] == "zipf_pool":
        states = draw_from_pool(rng, traffic, platform, seed, len(slots))
    else:
        names = draw_windows(rng, traffic["windows"], slots)
        masks = draw_masks(rng, traffic["masks"], platform,
                           [(nm, b) for nm, (b, _) in zip(names, slots)])
        states = [(nm, fam, free) for nm, (fam, free) in zip(names, masks)]
    out, k = [], 0
    for t, count, b in arrivals:
        for _ in range(count):
            nm, fam, free = states[k]
            out.append(Request(index=k, due=t, window=nm, free=free,
                               family=fam, burst=b))
            k += 1
    return out


def requests_like(rng: np.random.Generator, masks: Dict, platform: Dict,
                  template: Request, count: int) -> List[Request]:
    """``count`` requests of the template's window and mask family, each
    on a fresh draw of the family's extra busy engines."""
    rows, cols = platform["noc_rows"], platform["noc_cols"]
    base = (np.ones(rows * cols, bool) if template.family == "full"
            else half_base(template.family, rows, cols))
    out = []
    for k in range(count):
        free = base.copy()
        free[rng.choice(np.flatnonzero(base), size=masks["extra_busy"],
                        replace=False)] = False
        out.append(Request(index=k, due=0.0, window=template.window,
                           free=free, family=template.family, burst=False))
    return out
