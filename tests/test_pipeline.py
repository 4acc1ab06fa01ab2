"""Device-resident drain pipeline: the host-sync census (one blocking
fetch per all-warm drain), pipelined-vs-serial bitwise parity, the
pooled carry path's program budget, carry buffer donation, the device
carry pool's row lifecycle, the pooled popcount index bookkeeping,
device-side selection of the served mapping and the Tier-2 fetch's
payload."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import graphs, pso
from repro.core.carries import CarryStore, DeviceCarryPool
from repro.core.matcher import collect_batch_results, collect_served_results
from repro.core.service import MatcherService, ServiceStats
from repro.kernels import pallas_compat

jax.config.update("jax_platform_name", "cpu")

CFG = pso.PSOConfig(num_particles=24, epochs=3, inner_steps=8,
                    early_exit=True)

# two distinct shape buckets: (8, 16) and (8, 32)
BUCKET_ARGS = ((6, 12), (5, 24))


def _planted(seed, n, m, edge_prob=0.35):
    key = jax.random.PRNGKey(seed)
    kq, kt = jax.random.split(key)
    q = graphs.random_dag(kq, n, edge_prob)
    g = graphs.embed_query_in_target(kt, q, m)
    return q, g


def _burst(svc, specs):
    """Submit [(seed, n, m), ...] and drain; deterministic keys."""
    for seed, n, m in specs:
        q, g = _planted(seed, n, m)
        svc.submit(q, g, key=jax.random.PRNGKey(seed),
                   workload_key=(f"w{n}x{m}", seed))
    return svc.drain()


def _warm_specs(svc, per_bucket=2, max_seeds=12):
    """Problem specs across both buckets whose carries revalidate (the
    all-warm drain workload): cold-drains candidates, keeps the ones a
    repeat drain serves at Tier 0."""
    specs = []
    for n, m in BUCKET_ARGS:
        cands = [(s, n, m) for s in range(max_seeds)]
        _burst(svc, cands)
        warm = _burst(svc, cands)
        good = [c for c, r in zip(cands, warm) if r.tier == 0 and r.found]
        assert len(good) >= per_bucket, f"no warm problems for {(n, m)}"
        specs.extend(good[:per_bucket])
    return specs


# ---------------------------------------------------------------------------
# host-sync census / transfer guard
# ---------------------------------------------------------------------------

def test_warm_drain_costs_one_host_sync():
    """An all-warm multi-bucket pipelined drain resolves through exactly
    ONE blocking device→host fetch — asserted by the census counter, and
    additionally run under JAX's implicit-transfer guard (which traps
    stray ``np.asarray`` round trips on accelerator backends; CPU arrays
    are host-resident, so the counter is the hard assertion)."""
    svc = MatcherService(CFG)
    specs = _warm_specs(svc)
    # problem construction (host-side RNG sampling) happens before the
    # guard: only the submit+drain round must be implicit-transfer-free
    probs = [(_planted(seed, n, m), seed, n, m) for seed, n, m in specs]
    syncs0, drains0 = svc.stats.host_syncs, svc.stats.drains
    with jax.transfer_guard_device_to_host("disallow"):
        for (q, g), seed, n, m in probs:
            svc.submit(q, g, key=jax.random.PRNGKey(seed),
                       workload_key=(f"w{n}x{m}", seed))
        results = svc.drain()
    assert svc.stats.drains - drains0 == 1
    assert svc.stats.host_syncs - syncs0 == 1
    assert all(r.tier == 0 and r.found for r in results)
    assert svc.stats.host_bytes_transferred > 0
    assert svc.stats.host_sync_wall_s >= 0.0


def test_serial_arm_pays_a_sync_per_launch_and_per_carry():
    """``pipelined=False`` restores the legacy drain discipline: one
    blocking fetch per Tier-0 launch PLUS host numpy staging of every
    stored carry — three ``np.asarray`` transfers per warm item (S*, f*,
    S̄ are all device-pool residents). Two buckets → two launches → two
    explicit fetches, and 3 implicit syncs per warm item on top."""
    svc = MatcherService(CFG, pipelined=False)
    specs = _warm_specs(svc)
    syncs0 = svc.stats.host_syncs
    t0_launches0 = svc.stats.tier0.launches
    results = _burst(svc, specs)
    assert all(r.tier == 0 for r in results)
    launches = svc.stats.tier0.launches - t0_launches0
    assert launches == 2
    assert svc.stats.host_syncs - syncs0 == launches + 3 * len(specs)


def test_stats_dict_exports_census():
    svc = MatcherService(CFG)
    _burst(svc, [(0, 6, 12)])
    d = svc.stats_dict()
    for k in ("drains", "host_syncs", "host_syncs_per_drain",
              "host_bytes_transferred", "host_sync_wall_s",
              "donated_launches", "pool_puts", "pool_gathers",
              "pool_live_rows"):
        assert k in d, k
    assert d["drains"] == 1
    assert d["host_syncs"] >= 1


# ---------------------------------------------------------------------------
# pipelined vs serial parity
# ---------------------------------------------------------------------------

def _result_fingerprint(r):
    return (None if r.mapping is None else np.asarray(r.mapping).tobytes(),
            r.found, r.tier, r.f_star, r.epochs_run)


@pytest.mark.parametrize("tiered", [True, False],
                         ids=["tiered", "untiered"])
def test_pipelined_matches_serial_bitwise(tiered):
    """Async dispatch must not change a single bit of any result: a
    mixed easy/hard two-bucket burst produces identical mappings, tiers,
    f* and epoch counts through both resolve choices of the tier walk,
    cold AND warm, with the tiers on or off."""
    specs = [(s, n, m) for n, m in BUCKET_ARGS for s in range(5)]
    pipe = MatcherService(CFG, tiered=tiered)
    ser = MatcherService(CFG, tiered=tiered, pipelined=False)
    for _round in range(3):
        rp = _burst(pipe, specs)
        rs = _burst(ser, specs)
        for a, b in zip(rp, rs):
            assert _result_fingerprint(a) == _result_fingerprint(b)
    if not tiered:
        assert all(r.tier == 2 for r in rp)
        assert pipe.stats.tier0.launches == ser.stats.tier0.launches == 0


# ---------------------------------------------------------------------------
# the pooled carry path: one assembly and one write-back program a launch
# ---------------------------------------------------------------------------

# free-engine signatures of three platform states of one workload: "more"
# adds target edges to "base" (its stored carry rebases and usually
# revalidates: a Tier-1 hit); "other" plants the query elsewhere (the
# rebase usually fails: a Tier-1 miss that swarms from its rebased seed)
_SIGS = {"base": b"\x0f\x00", "more": b"\x0f\x01", "other": b"\x0e\x10"}

# drains of (query seed, n, m, platform state); buckets (8, 16), (8, 32)
_A, _B = (6, 12), (5, 24)
_DRAINS = {
    # batch classes 1, 2, 4 (one pad slot) and 8, all cold priors
    "cold": [[(s, *_A, "base") for s in range(3)] + [(0, *_B, "base")],
             [(s, *_A, "base") for s in range(3, 11)]
             + [(s, *_B, "base") for s in (1, 2)]],
    # exact repeats: Tier-0 hits, and failed exact carries that swarm
    "warm": [[(s, *_A, "base") for s in range(5)]
             + [(s, *_B, "base") for s in range(2)]] * 2,
    # Tier-0 hits, Tier-1 hits, Tier-1 misses with seeds, cold requests
    "mixed": [[(s, *_A, "base") for s in range(6)]
              + [(s, *_B, "base") for s in range(3)],
              [(0, *_A, "base"), (1, *_A, "base")]
              + [(s, *_A, "more") for s in range(2, 6)]
              + [(s, *_A, "other") for s in range(4)]
              + [(6, *_A, "base"), (7, *_A, "base"),
                 (0, *_B, "base"), (1, *_B, "more"), (2, *_B, "other"),
                 (3, *_B, "base")]],
}


@functools.lru_cache(maxsize=None)
def _state(seed, n, m, var):
    """(query, target, workload key) of one platform state."""
    kq, kt = jax.random.split(jax.random.PRNGKey(seed))
    q = graphs.random_dag(kq, n, 0.35)
    if var == "more":
        g = graphs.embed_query_in_target(kt, q, m, extra_edge_prob=0.3)
    elif var == "other":
        g = graphs.embed_query_in_target(jax.random.fold_in(kt, 1), q, m)
    else:
        g = graphs.embed_query_in_target(kt, q, m)
    return q, g, (f"w{seed}/{n}x{m}", _SIGS[var])


def _drain_states(svc, specs, host_keys=True):
    for i, spec in enumerate(specs):
        q, g, wk = _state(*spec)
        key = jax.random.PRNGKey(1000 + i)
        svc.submit(q, g, key=np.asarray(key) if host_keys else key,
                   workload_key=wk, engine_sig=wk[1])
    return svc.drain()


def _stored(svc):
    """Both carry stores as {key: host bytes}. (Their recency order
    across buckets differs between the arms: the pipelined drain runs
    each tier for every bucket before the next tier.)"""
    exact, sim = svc._carries.export_state()
    return [{k: tuple(np.asarray(p, np.float32).tobytes()
                      for p in svc._carry_tuple(c)) for k, c in items}
            for items in (exact, sim)]


@pytest.mark.parametrize("case", sorted(_DRAINS))
def test_pooled_drain_matches_serial_arm_bitwise(case):
    """The pooled carry path (assembly from pool rows and cold priors,
    one write-back per launch) serves and stores bitwise what the
    host-staged ``pipelined=False`` arm does, over cold, mixed and warm
    multi-bucket drains; every stored carry is the launch output the
    result was collected from."""
    pipe = MatcherService(CFG)
    ser = MatcherService(CFG, pipelined=False)
    for specs in _DRAINS[case]:
        rp = _drain_states(pipe, specs, host_keys=case != "cold")
        rs = _drain_states(ser, specs, host_keys=case != "cold")
        for a, b in zip(rp, rs):
            assert _result_fingerprint(a) == _result_fingerprint(b)
        assert _stored(pipe) == _stored(ser)
        assert pipe._pool.live_rows == ser._pool.live_rows
        for spec, r in zip(specs, rp):
            if r.tier == 0:
                continue
            q, g, wk = _state(*spec)
            stored = pipe._carries._exact[
                pipe._warm_key(pipe._prepare(q, g, None, wk))]
            for got, want in zip(pipe._carry_tuple(stored), r.carry):
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(want, np.float32))
    s = pipe.stats_dict()
    if case != "warm":
        assert s["pad_slots_frozen"] > 0
    if case != "cold":
        assert s["tier0_hits"] > 0
    if case == "mixed":
        assert 0 < s["tier1_hits"] < s["tier1_checked"]
    assert s["pool_writes"] > 0 and s["pool_gathers"] > 0


def test_carry_path_glue_budget(monkeypatch):
    """One write-back program per launch that stores rows, no slicing of
    launch outputs in either apply, and a seed's pool row back on the
    free list once its swarm launch is dispatched."""
    svc = MatcherService(CFG)
    _drain_states(svc, _DRAINS["mixed"][0])

    array_type = type(jnp.zeros(1))
    in_apply, sliced = [], []
    get_item = array_type.__getitem__

    def spy_getitem(self, idx):
        if in_apply:
            sliced.append(idx)
        return get_item(self, idx)

    monkeypatch.setattr(array_type, "__getitem__", spy_getitem)
    storing, rows, seeds = [], [], []

    def wrap_apply(name):
        orig = getattr(svc, name)

        def apply(rec, host):
            in_apply.append(rec)
            try:
                orig(rec, host)
            finally:
                in_apply.pop()
            # Tier 0 stores nothing; Tier 1 its hits and held seeds;
            # Tier 2 every item
            stored = [it for it in rec.items if rec.tier == 2
                      or rec.tier == 1 and (it.result is not None
                                            or it.seed is not None)]
            if stored:
                storing.append(rec)
                rows.append(len(stored))
        monkeypatch.setattr(svc, name, apply)

    wrap_apply("_apply_swarm")
    wrap_apply("_apply_revalidate")
    dispatch_swarm = svc._dispatch_swarm

    def dispatch(bucket, items):
        held = [it.seed for it in items if it.seed is not None]
        rec = dispatch_swarm(bucket, items)
        for h in held:
            seeds.append(h)
            assert h.row == -1 and h.refs == 0
        return rec

    monkeypatch.setattr(svc, "_dispatch_swarm", dispatch)
    before = svc.stats_dict()
    _drain_states(svc, _DRAINS["mixed"][1])
    after = svc.stats_dict()
    assert sliced == []
    assert after["pool_writes"] - before["pool_writes"] == len(storing) > 0
    assert after["pool_puts"] - before["pool_puts"] == sum(rows)
    assert seeds, "the mixed drain has no Tier-1 miss that swarms"
    # every live row is held by a store entry or a pad: no seed leaked
    held = {id(c) for items in svc._carries.export_state()
            for _, c in items} | {id(h) for h in svc._pad_handles.values()}
    assert svc._pool.live_rows == len(held)


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------

def test_donation_does_not_change_results():
    """donate_buffers only changes buffer lifetime, never values; the
    donated arm actually donates when the toolchain supports it and the
    opted-out arm never counts a donated launch."""
    specs = [(s, 6, 12) for s in range(5)]
    on = MatcherService(CFG, donate_buffers=True)
    off = MatcherService(CFG, donate_buffers=False)
    for _round in range(2):
        ra = _burst(on, specs)
        rb = _burst(off, specs)
        for a, b in zip(ra, rb):
            assert _result_fingerprint(a) == _result_fingerprint(b)
    assert off.stats.donated_launches == 0
    if pallas_compat.donation_supported():
        assert on.stats.donated_launches > 0


def test_donation_probe_is_cached_bool():
    assert isinstance(pallas_compat.donation_supported(), bool)
    assert isinstance(pallas_compat.export_preserves_donation(), bool)
    assert pallas_compat.donation_supported() \
        == pallas_compat.donation_supported()


# ---------------------------------------------------------------------------
# DeviceCarryPool lifecycle
# ---------------------------------------------------------------------------

def _carry(n=4, m=8, fill=1.0, f=2.5):
    S = np.full((n, m), fill, np.float32)
    return (S, np.float32(f), S * 0.5)


def test_pool_put_gather_roundtrip():
    pool = DeviceCarryPool(block=4)
    carries = [_carry(fill=float(i), f=float(i)) for i in range(3)]
    handles = [pool.put(c) for c in carries]
    # the assembly program gathers rows and fills None slots with the
    # cold prior of their mask
    maskb = np.zeros((4, 4, 8), np.uint8)
    maskb[3, :, :2] = 1
    S, f, C = pool.assemble(handles + [None], jnp.asarray(maskb))
    assert S.shape == (4, 4, 8)
    np.testing.assert_array_equal(
        np.asarray(f), np.asarray([0.0, 1.0, 2.0, -np.inf], np.float32))
    for i, h in enumerate(handles):
        s_i, f_i, c_i = h.materialize()
        np.testing.assert_array_equal(np.asarray(s_i), carries[i][0])
        np.testing.assert_array_equal(np.asarray(c_i), carries[i][2])
        np.testing.assert_array_equal(np.asarray(S[i]), carries[i][0])
        np.testing.assert_array_equal(np.asarray(C[i]), carries[i][2])
    cold = pso.default_carry(jnp.asarray(maskb[3]))
    np.testing.assert_array_equal(np.asarray(S[3]), np.asarray(cold[0]))
    np.testing.assert_array_equal(np.asarray(C[3]), np.asarray(cold[2]))
    assert pool.gathers == 1
    assert pool.puts == 3
    assert pool.writes == 3


def test_pool_rows_recycle_on_release():
    pool = DeviceCarryPool(block=2)
    h1, h2 = pool.put(_carry(fill=1.0)), pool.put(_carry(fill=2.0))
    cap0 = pool._slabs[(4, 8)]["cap"]
    row1 = h1.row
    h1.retain()
    h1.release()                       # last ref -> row back to free list
    assert pool.live_rows == 1
    h3 = pool.put(_carry(fill=3.0))    # reuses the freed row, no growth
    assert h3.row == row1
    assert pool._slabs[(4, 8)]["cap"] == cap0
    assert pool.live_rows == 2
    np.testing.assert_array_equal(np.asarray(h3.materialize()[0]),
                                  np.full((4, 8), 3.0, np.float32))
    np.testing.assert_array_equal(np.asarray(h2.materialize()[0]),
                                  np.full((4, 8), 2.0, np.float32))


def test_pool_slab_grows_geometrically():
    pool = DeviceCarryPool(block=2)
    handles = [pool.put(_carry(fill=float(i))) for i in range(5)]
    assert pool._slabs[(4, 8)]["cap"] >= 5
    for i, h in enumerate(handles):
        assert float(np.asarray(h.materialize()[0])[0, 0]) == float(i)


def test_store_eviction_frees_pool_rows():
    """Warm-store evictions release their handles, so the pool's live
    rows stay bounded by the store capacities however many problems
    flow through the service."""
    svc = MatcherService(CFG, warm_capacity=3, sim_capacity=2)
    specs = [(s, 6, 12) for s in range(8)]
    _burst(svc, specs)
    _burst(svc, specs)
    # 3 exact + 2 sim + 1 pinned pad handle upper-bounds the live rows
    assert svc._pool.live_rows <= 3 + 2 + len(svc._pad_handles)
    assert len(svc._carries) <= 3


# ---------------------------------------------------------------------------
# CarryStore: popcount-at-ingest + handle refcounts
# ---------------------------------------------------------------------------

class _FakeHandle:
    def __init__(self):
        self.refs = 0

    def retain(self):
        self.refs += 1

    def release(self):
        self.refs -= 1


def test_store_retains_and_releases_handles():
    cs = CarryStore(capacity=2, sim_capacity=2, stats=ServiceStats())
    h1, h2, h3 = _FakeHandle(), _FakeHandle(), _FakeHandle()
    cs.put("a", h1)
    cs.put("b", h2)
    assert (h1.refs, h2.refs) == (1, 1)
    cs.put("a", h3)                    # overwrite releases the old value
    assert (h1.refs, h3.refs) == (0, 1)
    # put does not refresh recency (only get does), so "a" is still the
    # LRU entry and its new handle is released on eviction
    cs.put("c", _FakeHandle())
    assert h3.refs == 0
    cs.clear()
    assert h2.refs == 0


def test_sim_popcount_computed_once_at_ingest():
    cs = CarryStore(capacity=4, sim_capacity=2, stats=ServiceStats())
    sigs = [bytes([0b1010]), bytes([0b1110]), bytes([0b0001])]
    for i, sig in enumerate(sigs):
        cs.put_similar("qd", (8, 16), sig, i)
    # capacity 2: first entry evicted, index/popcount cache follow along
    assert cs.sim_entries == 2
    assert set(cs._sim_pop) == set(cs._sim)
    for key, pc in cs._sim_pop.items():
        assert pc == int(cs._sim[key][0].sum())
    nb = cs.nearest("qd", (8, 16), bytes([0b0110]))
    assert nb is not None and nb[1] == 1  # overlaps the 0b1110 entry


# ---------------------------------------------------------------------------
# device-side selection of the served mapping (pso.best_feasible)
# ---------------------------------------------------------------------------

def _outs(feasible, fitness, maps):
    """A (T, B, N) trace with the problem axis B after the epoch axis."""
    return {"feasible": jnp.asarray(feasible),
            "fitness": jnp.asarray(fitness, jnp.float32),
            "mappings": jnp.asarray(maps, jnp.uint8)}


def test_best_feasible_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    T, B, N = 2, 3, 4
    for _ in range(10):
        feas = rng.random((T, B, N)) < 0.3
        fit = rng.standard_normal((T, B, N)).astype(np.float32)
        maps = rng.integers(0, 2, (T, B, N, 3, 5)).astype(np.uint8)
        got, found = pso.best_feasible(_outs(feas, fit, maps))
        for b in range(B):
            f_b = feas[:, b].reshape(-1)
            assert bool(found[b]) == f_b.any()
            if not f_b.any():
                np.testing.assert_array_equal(np.asarray(got[b]), 0)
                continue
            idx = np.where(f_b)[0]
            want = maps[:, b].reshape(-1, 3, 5)[
                idx[np.argmax(fit[:, b].reshape(-1)[idx])]]
            np.testing.assert_array_equal(np.asarray(got[b]), want)


def test_best_feasible_neginf_feasible_still_wins():
    """A feasible particle at f=-inf must beat infeasible slots (the
    masked score floor cannot shadow real entries)."""
    maps = np.stack([np.eye(3, 5, dtype=np.uint8) * i for i in range(3)])
    got, found = pso.best_feasible(_outs(
        [[[False, True, False]]], [[[1.0, -np.inf, 2.0]]], maps[None, None]))
    assert bool(found[0])
    np.testing.assert_array_equal(np.asarray(got[0]), maps[1])


def test_best_feasible_none_when_infeasible():
    maps = np.ones((1, 1, 2, 3, 5), np.uint8)
    got, found = pso.best_feasible(_outs([[[False, False]]], [[[0.0, 1.0]]],
                                         maps))
    assert not bool(found[0])
    np.testing.assert_array_equal(np.asarray(got[0]), 0)


def _batch_trace(rng, T=3, B=3, N=5, n=4, m=6, K=2, p_feas=0.3):
    """A full ``match_batch`` output pytree of random values."""
    return {
        "mappings": rng.integers(0, 2, (T, B, N, n, m)).astype(np.uint8),
        "feasible": rng.random((T, B, N)) < p_feas,
        "fitness": rng.standard_normal((T, B, N)).astype(np.float32),
        "f_star_trace": rng.standard_normal((T, B, K)).astype(np.float32),
        "S_star": rng.random((B, n, m)).astype(np.float32),
        "f_star": rng.standard_normal(B).astype(np.float32),
        "S_bar": rng.random((B, n, m)).astype(np.float32),
        "epochs_run": rng.integers(0, T + 1, B).astype(np.int32),
        "carry_mapping": rng.integers(0, 2, (B, n, m)).astype(np.uint8),
        "carry_feasible": np.zeros(B, bool),
        "prune_sweeps": rng.integers(0, 5, B).astype(np.int32),
    }


def _selection_case(case):
    """(trace, problems collected, orders, crops) of one exactness case."""
    rng = np.random.default_rng(sorted(_SELECTION_CASES).index(case))
    outs = _batch_trace(rng)
    T, B, N, n, m = outs["mappings"].shape
    feas, fit = outs["feasible"], outs["fitness"]
    batch = B
    orders = [np.arange(n)] * B
    crops = [(n, m)] * B
    if case == "ties":
        feas[:] = rng.random(feas.shape) < 0.6
        fit[:] = np.round(fit)            # many equal fitness values
        fit[1, 0, :] = fit.max() + 1.0    # epoch 1 ties across particles
    elif case == "neginf_feasible":
        feas[:] = False
        feas[2, 0, 3] = feas[1, 1, 0] = feas[2, 1, 1] = True
        fit[:, :2] = -np.inf
        fit[2, 1, 1] = -np.inf
    elif case == "nan_fitness":
        feas[:] = True
        fit[0, 0, 4] = fit[2, 0, 1] = np.nan     # first NaN wins
        fit[1, 1, 2] = np.nan
        feas[0, 2, 0] = False
        fit[0, 2, 0] = np.nan                    # infeasible NaN ignored
    elif case == "all_infeasible":
        feas[:] = False
    elif case == "carry_fastpath":
        feas[:, :2] = False
        outs["carry_feasible"][:] = [True, False, True]
    elif case == "pad_slots":
        outs = _batch_trace(rng, B=8)
        outs["carry_feasible"][3:] = True        # pre-finished pad slots
        outs["feasible"][:, 3:] = False
        batch = 3
        orders, crops = [np.arange(n)] * 8, [(n, m)] * 8
    elif case == "crops_orders":
        crops = [(3, 5), (4, 4), (2, 6)]
        orders = [rng.permutation(c[0]) for c in crops]
        outs["carry_feasible"][2] = True
        feas[:, 2] = False
    return outs, batch, orders, crops


_SELECTION_CASES = ("random", "ties", "neginf_feasible", "nan_fitness",
                    "all_infeasible", "carry_fastpath", "pad_slots",
                    "crops_orders")


@pytest.mark.parametrize("case", _SELECTION_CASES)
def test_served_selection_equals_collect_result(case):
    """The swarm program's on-device choice of each problem's served
    mapping, collected from the fetched subset, equals
    ``collect_result`` on the full trace: mapping, found, counts,
    scalars and the (T·N,) flags and fitness."""
    outs, batch, orders, crops = _selection_case(case)
    want = collect_batch_results(outs, outs["f_star"].shape[0],
                                 orders=orders, crops=crops)[:batch]
    served = jax.device_get(jax.jit(pso.served_outs)(outs))
    assert "mappings" not in served and "carry_mapping" not in served
    got = collect_served_results(served, batch, orders, crops)
    assert len(got) == batch
    for g, w, (n, m) in zip(got, want, crops):
        assert g.found == w.found
        if w.found:
            np.testing.assert_array_equal(g.mapping, w.mapping)
            assert g.mapping.dtype == w.mapping.dtype
        assert g.feasible_count == w.feasible_count
        assert g.epochs_run == w.epochs_run
        assert g.carry_verified == w.carry_verified
        assert g.prune_sweeps == w.prune_sweeps
        assert g.f_star == w.f_star
        np.testing.assert_array_equal(g.f_star_trace, w.f_star_trace)
        np.testing.assert_array_equal(g.all_feasible, w.all_feasible)
        np.testing.assert_array_equal(g.all_fitness, w.all_fitness)
        assert g.all_mappings.shape == (0, n, m)


def test_cold_drain_serves_what_match_batch_selects():
    """A cold two-bucket drain serves, per problem, the mapping, found
    flag, f*, epochs and feasible count that ``collect_batch_results``
    picks from ``pso.match_batch``'s full trace for the same keys."""
    groups = {(6, 12): [0, 1], (5, 24): [2]}     # batch classes 2 and 1
    svc = MatcherService(CFG)
    for (n, m), seeds in groups.items():
        for s in seeds:
            q, g = _planted(s, n, m)
            svc.submit(q, g, key=np.asarray(jax.random.PRNGKey(50 + s)))
    served = svc.drain()
    assert all(r.tier == 2 for r in served)
    want = []
    for (n, m), seeds in groups.items():
        reqs = [svc._prepare(*_planted(s, n, m), None, None) for s in seeds]
        outs = pso.match_batch(
            np.stack([np.asarray(jax.random.PRNGKey(50 + s))
                      for s in seeds]),
            np.stack([r.Qp for r in reqs]), np.stack([r.Gp for r in reqs]),
            np.stack([r.maskp for r in reqs]), svc.cfg)
        want += collect_batch_results(outs, len(seeds),
                                      orders=[r.order for r in reqs],
                                      crops=[r.crop for r in reqs])
    assert any(w.found for w in want)
    for g, w in zip(served, want):
        assert g.found == w.found
        if w.found:
            np.testing.assert_array_equal(g.mapping, w.mapping)
        assert g.f_star == w.f_star
        assert g.epochs_run == w.epochs_run
        assert g.feasible_count == w.feasible_count
        assert g.all_mappings.shape == (0, *w.all_mappings.shape[1:])


def _swarm_fetches(svc, monkeypatch, specs):
    """(bucket, bclass, leaf shapes and dtypes, fetched bytes) of every
    swarm launch a drain of ``specs`` applies."""
    seen = []
    apply = svc._apply_swarm

    def spy(rec, host):
        seen.append((rec.bucket, rec.bclass,
                     {k: (v.shape, v.dtype) for k, v in host.items()},
                     sum(v.nbytes for v in host.values())))
        apply(rec, host)

    monkeypatch.setattr(svc, "_apply_swarm", spy)
    _burst(svc, specs)
    return seen


def test_swarm_fetch_moves_served_subset_only(monkeypatch):
    """A cold Tier-2 drain fetches no per-particle n-by-m plane and no
    S*/S̄ plane, at most bclass × (n·m + 2 KiB) bytes a launch, counted
    by ``tier2_fetch_bytes``; both drain arms fetch the same subset."""
    specs = [(s, n, m) for n, m in BUCKET_ARGS for s in range(3)]
    arms = []
    for pipelined in (True, False):
        svc = MatcherService(CFG, pipelined=pipelined)
        before = svc.stats_dict()
        seen = _swarm_fetches(svc, monkeypatch, specs)
        after = svc.stats_dict()
        assert seen
        assert after["tier2_fetch_bytes"] - before["tier2_fetch_bytes"] \
            == sum(nb for *_, nb in seen)
        assert after["tier2_launches"] - before["tier2_launches"] \
            == len(seen)
        for (n, m), bclass, leaves, nbytes in seen:
            assert "S_star" not in leaves and "S_bar" not in leaves
            for shape, dtype in leaves.values():
                if shape[-2:] == (n, m):
                    assert shape == (bclass, n, m) and dtype == np.uint8
            assert nbytes <= bclass * (n * m + 2048)
        arms.append(seen)
    assert arms[0] == arms[1]


# ---------------------------------------------------------------------------
# snapshot round trip keeps the single-sync warm drain
# ---------------------------------------------------------------------------

def test_restored_snapshot_warm_drain_single_sync(tmp_path):
    svc = MatcherService(CFG, persist_dir=str(tmp_path))
    specs = _warm_specs(svc, per_bucket=2)
    _burst(svc, specs)
    svc.save_snapshot()

    svc2 = MatcherService(CFG, persist_dir=str(tmp_path))
    assert svc2.restore_snapshot() is not None
    syncs0 = svc2.stats.host_syncs
    results = _burst(svc2, specs)
    assert all(r.tier == 0 and r.found for r in results)
    assert svc2.stats.host_syncs - syncs0 == 1
