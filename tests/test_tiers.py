"""Tiered decision pipeline: batched revalidation (Tier 0), similarity
rebase (Tier 1), residual swarm (Tier 2), the two-level carry store under
fragmentation, pre-finished pad slots, mixed-burst scenario generation,
and per-tier scheduler accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.accel import EDGE
from repro.accel.target_graph import (free_engine_graph,
                                      free_engine_signature)
from repro.core import graphs, preemptible_dag, pso
from repro.core.carries import CarryStore
from repro.core.graphs import compatibility_mask
from repro.core.service import MatcherService, ServiceStats
from repro.core.pso import PSOConfig
from repro.sched import SimConfig, Simulator, get_scheduler
from repro.sched.tasks import fixed_scenario, make_mixed_burst_scenario
from repro.workloads import get_workload

jax.config.update("jax_platform_name", "cpu")

CFG = pso.PSOConfig(num_particles=24, epochs=3, inner_steps=8,
                    early_exit=True)


def _planted(seed, n, m, edge_prob=0.35):
    key = jax.random.PRNGKey(seed)
    kq, kt = jax.random.split(key)
    q = graphs.random_dag(kq, n, edge_prob)
    g = graphs.embed_query_in_target(kt, q, m)
    return q, g


def _check_mapping(mapping, q, g):
    assert mapping is not None
    M = np.asarray(mapping, dtype=np.int64)
    assert (M.sum(axis=1) == 1).all()
    assert (M.sum(axis=0) <= 1).all()
    covered = M @ g.adj.astype(np.int64) @ M.T
    assert (covered >= q.adj).all()


def _stack(pairs):
    Qs, Gs, Ms = [], [], []
    for q, g in pairs:
        Q, G, mask = graphs.as_device_graphs(q, g)
        Qs.append(Q)
        Gs.append(G)
        Ms.append(mask)
    return jnp.stack(Qs), jnp.stack(Gs), jnp.stack(Ms)


def _fastpath_pair(svc, seed, n=6, m=12, max_seeds=40):
    """A planted problem whose stored carry re-validates (Tier-0 hit on
    repeat) through ``svc`` — mirrors bench_batch's 'servable' filter."""
    for s in range(seed, seed + max_seeds):
        q, g = _planted(s, n, m)
        key = jax.random.PRNGKey(s)
        wk = f"fp/{s}"
        r = svc.match(q, g, key=key, workload_key=wk)
        if not r.found:
            continue
        r2 = svc.match(q, g, key=jax.random.PRNGKey(s + 1000),
                       workload_key=wk)
        if r2.tier == 0:
            return (q, g), key, wk
    raise AssertionError("no fast-pathing planted problem found")


# ---------------------------------------------------------------------------
# pso.revalidate_batch
# ---------------------------------------------------------------------------

def test_revalidate_batch_matches_inkernel_fastpath():
    """The Tier-0 kernel must reach the same verdict AND mapping as the
    in-kernel warm-carry fast path for exact carries."""
    pairs = [_planted(s, 6, 12) for s in range(3)]
    Qb, Gb, maskb = _stack(pairs)
    keys = jnp.stack([np.asarray(jax.random.PRNGKey(i)) for i in range(3)])
    cold = pso.match_batch(keys, Qb, Gb, maskb, CFG)
    carry = (cold["S_star"], cold["f_star"], cold["S_bar"])
    rv = pso.revalidate_batch(Qb, Gb, maskb, CFG, carry)
    warm = pso.match_batch(keys, Qb, Gb, maskb, CFG, carry0=carry)
    np.testing.assert_array_equal(np.asarray(rv["ok"]),
                                  np.asarray(warm["carry_feasible"]))
    for b in range(3):
        if np.asarray(rv["ok"])[b]:
            np.testing.assert_array_equal(
                np.asarray(rv["mapping"])[b],
                np.asarray(warm["carry_mapping"])[b])


def test_revalidate_cold_prior_never_validates():
    pairs = [_planted(s, 6, 12) for s in range(2)]
    Qb, Gb, maskb = _stack(pairs)
    rv = pso.revalidate_batch(Qb, Gb, maskb, CFG,
                              pso.default_carry_batch(maskb))
    assert not np.asarray(rv["ok"]).any()


def test_rebased_carry_never_marks_infeasible_found():
    """A carry rebased onto a problem it cannot solve must fail
    revalidation — feasibility is re-checked against the actual Q/G."""
    easy_q, easy_g = _planted(2, 6, 12)
    Qe, Ge, me = graphs.as_device_graphs(easy_q, easy_g)
    keys = jnp.stack([np.asarray(jax.random.PRNGKey(0))])
    cold = pso.match_batch(keys, Qe[None], Ge[None], me[None], CFG)
    assert np.asarray(cold["feasible"]).any()
    carry = (cold["S_star"], cold["f_star"], cold["S_bar"])

    # an infeasible problem in the same shapes: line(6) into line(4)
    hq, hg = graphs.line_graph(6), graphs.line_graph(4)
    mask_h = compatibility_mask(hq, hg)
    Qh, Gh, mh = preemptible_dag.pad_problem(hq.adj, hg.adj, mask_h,
                                             Qe.shape[0], Ge.shape[0])
    rv = pso.revalidate_batch(jnp.asarray(Qh)[None], jnp.asarray(Gh)[None],
                              jnp.asarray(mh)[None], CFG, carry)
    assert not np.asarray(rv["ok"]).any()


def test_rebase_carry_masks_and_renormalizes():
    q, g = _planted(0, 6, 12)
    _, _, mask = graphs.as_device_graphs(q, g)
    carry = pso.default_carry(mask)
    # drop half the columns from the mask; rebase must renormalize rows
    mask2 = np.asarray(mask).copy()
    mask2[:, ::2] = 0
    S_rb, f, S_bar_rb = pso.rebase_carry(carry, jnp.asarray(mask2))
    S_rb = np.asarray(S_rb)
    assert (S_rb[:, ::2] == 0).all()
    rows = S_rb.sum(axis=1)
    np.testing.assert_allclose(rows[np.asarray(mask2).sum(1) > 0], 1.0,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# CarryStore
# ---------------------------------------------------------------------------

def _sig(free):
    return free_engine_signature(np.asarray(free, bool))


def test_carry_store_exact_lru_eviction_order():
    store = CarryStore(capacity=2, sim_capacity=4, stats=ServiceStats())
    store.put("a", 1)
    store.put("b", 2)
    store.get("a")                    # refresh a → b is now oldest
    store.put("c", 3)                 # evicts b
    assert store.get("a") == (1, True)
    assert store.get("b") == (None, False)
    assert store.get("c") == (3, True)
    assert store.stats.warm_evictions == 1


def test_carry_store_similarity_lru_eviction_order():
    stats = ServiceStats()
    store = CarryStore(capacity=4, sim_capacity=2, stats=stats)
    free = np.ones(16, bool)
    sigs = []
    for i in range(3):
        f = free.copy()
        f[i] = False
        sigs.append(_sig(f))
        store.put_similar("q", (8, 16), sigs[-1], carry=i)
    # capacity 2: the first (oldest) entry was evicted
    assert stats.sim_evictions == 1
    assert store.nearest("q", (8, 16), sigs[0]) is not None
    remaining = {s for (qd, bk, s) in store._sim}
    assert sigs[0] not in remaining and remaining == {sigs[1], sigs[2]}


def test_carry_store_nearest_picks_max_overlap():
    store = CarryStore(capacity=4, sim_capacity=8, stats=ServiceStats())
    base = np.zeros(16, bool)
    near = base.copy()
    near[:8] = True                   # 8 engines free
    far = base.copy()
    far[12:14] = True                 # disjoint pair
    store.put_similar("q", (8, 16), _sig(near), carry="near")
    store.put_similar("q", (8, 16), _sig(far), carry="far")
    query = base.copy()
    query[:6] = True                  # overlaps 'near' by 6, 'far' by 0
    got = store.nearest("q", (8, 16), _sig(query))
    assert got is not None and got[1] == "near"
    # disjoint query finds nothing (zero overlap is not a neighbour)
    query2 = base.copy()
    query2[14:16] = True
    assert store.nearest("q", (8, 16), _sig(query2)) is None
    # different workload digest or bucket never matches
    assert store.nearest("other", (8, 16), _sig(query)) is None
    assert store.nearest("q", (16, 32), _sig(query)) is None


# ---------------------------------------------------------------------------
# Service pipeline: drain tiers
# ---------------------------------------------------------------------------

def test_drain_pipeline_serves_warm_via_tier0_and_sizes_swarm_to_misses():
    svc = MatcherService(CFG)
    (q1, g1), k1, w1 = _fastpath_pair(svc, 100)
    (q2, g2), k2, w2 = _fastpath_pair(svc, 200)
    hq, hg = graphs.line_graph(6), graphs.line_graph(4)  # same bucket,
    s0 = svc.stats_dict()                                # infeasible
    res = svc.match_many([(q1, g1), (q2, g2), (hq, hg)],
                         keys=[k1, k2, jax.random.PRNGKey(9)],
                         workload_keys=[w1, w2, "hard"])
    s1 = svc.stats_dict()
    assert res[0].tier == 0 and res[1].tier == 0
    assert res[0].epochs_run == 0 and res[1].epochs_run == 0
    _check_mapping(res[0].mapping, q1, g1)
    _check_mapping(res[1].mapping, q2, g2)
    assert res[2].tier == 2 and not res[2].found
    # ONE revalidation launch for the warm pair...
    assert s1["tier0_launches"] - s0["tier0_launches"] == 1
    assert s1["tier0_hits"] - s0["tier0_hits"] == 2
    # ...and the swarm launch covered ONLY the residual miss
    assert s1["batch_launches"] - s0["batch_launches"] == 1
    assert s1["batch_problems"] - s0["batch_problems"] == 1
    assert res[2].batch_size == 1
    # the whole group still counts as one coalesced decision
    assert s1["coalesced_requests"] - s0["coalesced_requests"] == 3


def test_tiered_drain_matches_untiered_per_problem():
    """Warm or cold, the pipeline must return the same found flags and
    mappings as the untiered uniform-batch drain (PR-2 baseline)."""
    probs = [_planted(s, 6, 12) for s in range(4)]
    keys = [jax.random.PRNGKey(50 + i) for i in range(4)]
    wks = [f"w{i}" for i in range(4)]
    svc_t = MatcherService(CFG, tiered=True)
    svc_u = MatcherService(CFG, tiered=False)
    for svc in (svc_t, svc_u):
        svc.match_many(probs, keys=keys, workload_keys=wks)     # cold
    warm_t = svc_t.match_many(probs, keys=keys, workload_keys=wks)
    warm_u = svc_u.match_many(probs, keys=keys, workload_keys=wks)
    for rt, ru in zip(warm_t, warm_u):
        assert rt.found == ru.found
        assert rt.epochs_run == ru.epochs_run
        if rt.found:
            np.testing.assert_array_equal(np.asarray(rt.mapping),
                                          np.asarray(ru.mapping))
    # the untiered walk runs no Tier-0/1 launch: every problem swarms
    su = svc_u.stats_dict()
    assert su["tier0_launches"] == su["tier1_launches"] == 0
    assert su["tier2_checked"] == su["batch_problems"] == su["calls"] == 8
    assert all(r.tier == 2 for r in warm_u)


def test_tier1_rebase_after_engine_drift():
    """Same workload, drifted free-engine set (same bucket): the pipeline
    serves it by rebasing the nearest stored carry — 0 epochs, and the
    mapping is feasible on the NEW target."""
    svc = MatcherService(PSOConfig(num_particles=32, epochs=3,
                                   inner_steps=8))
    wl = get_workload("mobilenetv2")
    cap = EDGE.engine_tile_capacity_macs()
    pd = preemptible_dag.build_preemptible_dag(
        [(0, wl, 0)], tile_capacity_macs=cap, window_stages=2)
    q = pd.graph
    rng = np.random.default_rng(0)

    def state(n_busy):
        free = np.ones(EDGE.engines, bool)
        free[rng.choice(EDGE.engines, n_busy, replace=False)] = False
        return free_engine_graph(EDGE, free), free_engine_signature(free)

    tgt_a, sig_a = state(6)
    r1 = svc.match(q, tgt_a, key=jax.random.PRNGKey(0),
                   workload_key=("mb", sig_a))
    assert r1.found
    # drift within the same shape bucket (same free count, different set)
    hit = False
    for trial in range(1, 6):
        tgt_b, sig_b = state(6)
        if sig_b == sig_a:
            continue
        r2 = svc.match(q, tgt_b, key=jax.random.PRNGKey(trial),
                       workload_key=("mb", sig_b))
        assert r2.bucket == r1.bucket
        assert not r2.warm_hit          # content key missed (drift)
        if r2.tier == 1:
            hit = True
            assert r2.epochs_run == 0 and r2.found
            _check_mapping(r2.mapping, q, tgt_b)
            break
    assert hit, "no drifted state was served by a Tier-1 rebase"
    s = svc.stats_dict()
    assert s["sim_neighbor_hits"] >= 1 and s["tier1_hits"] >= 1


def test_tier1_rebase_in_batched_drain():
    """Tier-1 rebases also run inside drain's batched pipeline."""
    svc = MatcherService(PSOConfig(num_particles=32, epochs=3,
                                   inner_steps=8))
    wl = get_workload("mobilenetv2")
    cap = EDGE.engine_tile_capacity_macs()
    pd = preemptible_dag.build_preemptible_dag(
        [(0, wl, 0)], tile_capacity_macs=cap, window_stages=2)
    q = pd.graph
    rng = np.random.default_rng(1)
    free_a = np.ones(EDGE.engines, bool)
    free_a[rng.choice(EDGE.engines, 6, replace=False)] = False
    tgt_a = free_engine_graph(EDGE, free_a)
    sig_a = free_engine_signature(free_a)
    svc.match(q, tgt_a, key=jax.random.PRNGKey(0),
              workload_key=("mb", sig_a))

    served = False
    for trial in range(1, 6):
        free_b = np.ones(EDGE.engines, bool)
        free_b[rng.choice(EDGE.engines, 6, replace=False)] = False
        sig_b = free_engine_signature(free_b)
        if sig_b == sig_a:
            continue
        tgt_b = free_engine_graph(EDGE, free_b)
        svc.submit(q, tgt_b, key=jax.random.PRNGKey(trial),
                   workload_key=("mb", sig_b))
        res = svc.drain()
        if res[0].tier == 1:
            served = True
            assert res[0].epochs_run == 0
            _check_mapping(res[0].mapping, q, tgt_b)
            break
    assert served
    assert svc.stats_dict()["tier1_launches"] >= 1


def test_drain_without_similarity_never_rebases():
    svc = MatcherService(CFG, similarity=False)
    q, g = _planted(0, 6, 12)
    svc.match(q, g, workload_key=("w", b"\x0f"))
    svc.match(q, g, workload_key=("w", b"\xf0"))
    s = svc.stats_dict()
    assert s["sim_lookups"] == 0 and s["tier1_launches"] == 0


# ---------------------------------------------------------------------------
# Pad slots (service.py padded-batch waste fix)
# ---------------------------------------------------------------------------

def test_pad_slots_prefinished_from_epoch_zero():
    """Pad slots run a trivial pre-finished problem: its carry validates
    in epoch 0, so the pad never re-burns problem 0's epoch budget."""
    svc = MatcherService(CFG)
    probs = [_planted(s, 6, 12) for s in range(3)]    # class 4 → 1 pad
    res = svc.match_many(probs,
                         keys=[jax.random.PRNGKey(i) for i in range(3)])
    assert len(res) == 3
    assert svc.stats.pad_slots_frozen == 1

    # pso-level: the trivial pad problem + carry is done at epoch 0
    req0 = svc._prepare(probs[0][0], probs[0][1], None, None)
    pad_req, pad_carry = svc._pad_slot(res[0].bucket, req0, None)
    assert pad_req is not req0
    outs = pso.match(jax.random.PRNGKey(0), jnp.asarray(pad_req.Qp),
                     jnp.asarray(pad_req.Gp), jnp.asarray(pad_req.maskp),
                     CFG, carry0=tuple(jnp.asarray(c) for c in pad_carry))
    assert int(np.asarray(outs["epochs_run"])) == 0
    assert bool(np.asarray(outs["carry_feasible"]))


def test_pad_slot_degenerate_bucket_falls_back_to_replication():
    svc = MatcherService(CFG)
    q, g = _planted(0, 6, 12)
    req = svc._prepare(q, g, None, None)
    like_carry = pso.default_carry(jnp.asarray(req.maskp))
    pad_req, pad_carry = svc._pad_slot((24, 16), req, like_carry)
    assert pad_req is req and pad_carry is like_carry


# ---------------------------------------------------------------------------
# Scenario generator
# ---------------------------------------------------------------------------

def test_make_mixed_burst_scenario_shapes_and_churn():
    sc = make_mixed_burst_scenario("simple", "complex", rate_hz=30,
                                   horizon=0.5, burst_size=6,
                                   hard_frac=0.34, burst_frac=0.9,
                                   churn_rate_hz=20, seed=3)
    from collections import Counter
    by_instant = {}
    for t in sc.tasks:
        by_instant.setdefault(t.arrival, []).append(t)
    sizes = Counter(len(v) for v in by_instant.values())
    assert max(sizes) == 6, "full bursts share one instant"
    from repro.workloads import workload_complexity_class
    easy_names = {w.name for w in workload_complexity_class("simple")}
    hard_names = {w.name for w in workload_complexity_class("complex")}
    mixed = [v for v in by_instant.values() if len(v) == 6]
    assert any({t.name for t in v} & easy_names and
               {t.name for t in v} & hard_names for v in mixed), \
        "bursts must mix easy and hard workloads"
    churn = [t for t in sc.tasks if t.urgent]
    assert churn, "churn stream must produce urgent tasks"
    assert all(t.name in easy_names for t in churn)
    # determinism
    sc2 = make_mixed_burst_scenario("simple", "complex", rate_hz=30,
                                    horizon=0.5, burst_size=6,
                                    hard_frac=0.34, burst_frac=0.9,
                                    churn_rate_hz=20, seed=3)
    assert [(t.name, t.arrival, t.urgent) for t in sc.tasks] == \
           [(t.name, t.arrival, t.urgent) for t in sc2.tasks]


# ---------------------------------------------------------------------------
# Scheduler accounting
# ---------------------------------------------------------------------------

def test_immsched_tier_counters_surface_in_matcher_stats():
    sc = make_mixed_burst_scenario("simple", "simple", rate_hz=40,
                                   horizon=0.4, burst_size=4,
                                   hard_frac=0.0, burst_frac=0.8, seed=2)
    cfg = SimConfig(platform=EDGE, matcher_mode="analytic")
    r = Simulator(cfg, get_scheduler("immsched")).run(sc)
    ms = r.matcher_stats
    total = sum(ms[f"sched_tier{i}_decisions"] for i in range(3))
    assert total > 0
    assert ms["sched_tier2_decisions"] > 0          # cold starts swarm
    # repeat traffic on a stable platform state revalidates
    assert ms["sched_tier0_decisions"] + ms["sched_tier1_decisions"] > 0
    from repro.sched.metrics import pipeline_tier_rates
    rates = pipeline_tier_rates(r)
    assert abs(sum(rates[f"sched_tier{i}_rate"] for i in range(3)) - 1.0) \
        < 1e-9


def test_immsched_revalidate_cost_below_swarm_cost():
    from repro.accel import CostModel
    cost = CostModel(EDGE)
    cfg = PSOConfig(num_particles=32, epochs=2, inner_steps=8)
    st_s, se_s = cost.sched_immsched(48, EDGE.engines, cfg, 16)
    st_r, se_r = cost.sched_immsched_revalidate(48, EDGE.engines, 16)
    assert st_r < st_s / 5
    assert se_r < se_s / 5


def test_isosched_memo_warms_repeat_traffic():
    wls = [get_workload("mobilenetv2")] * 4
    sc = fixed_scenario(wls, urgent_last=False)
    cfg = SimConfig(platform=EDGE, matcher_mode="analytic")
    r = Simulator(cfg, get_scheduler("isosched")).run(sc)
    assert r.matcher_stats["memo_hits"] > 0
    assert r.matcher_stats["memo_misses"] >= 1


# ---------------------------------------------------------------------------
# Popcount-bucketed similarity index (PR 4)
# ---------------------------------------------------------------------------

def test_carry_store_index_matches_linear_scan():
    """Property sweep: the popcount-bucketed probe must return exactly
    what the exhaustive linear scan returns — same neighbour, same carry
    — including overwrite/recency ties, exclusions and shape-mismatched
    signatures."""
    rng = np.random.default_rng(0)
    store = CarryStore(capacity=4, sim_capacity=4096, stats=ServiceStats())
    E = 32
    sigs = []
    for i in range(300):
        bits = rng.random(E) < rng.uniform(0.05, 0.95)
        sig = _sig(bits)
        store.put_similar("q", (8, 16), sig, carry=("c", i))
        sigs.append(sig)
    # overwrite some entries (recency tie-break churn)
    for i in rng.choice(len(sigs), 50, replace=False):
        store.put_similar("q", (8, 16), sigs[i], carry=("c2", int(i)))
    # a second workload group and a shorter-signature group: neither may
    # leak into "q"/(8, 16)/32-bit queries
    for i in range(40):
        store.put_similar("other", (8, 16),
                          _sig(rng.random(E) < 0.5), carry=("o", i))
        store.put_similar("q", (8, 16),
                          _sig(rng.random(16) < 0.5), carry=("short", i))
    for trial in range(60):
        q_bits = rng.random(E) < rng.uniform(0.0, 1.0)
        q_sig = _sig(q_bits)
        excl = sigs[int(rng.integers(len(sigs)))] if trial % 3 == 0 else None
        got = store.nearest("q", (8, 16), q_sig, exclude_sig=excl)
        want = store._nearest_linear("q", (8, 16), q_sig, exclude_sig=excl)
        assert got == want
    # exact-signature queries must return their own entry under both paths
    # (an all-zero signature legitimately has no neighbour)
    for i in (0, 17, 123):
        got = store.nearest("q", (8, 16), sigs[i])
        want = store._nearest_linear("q", (8, 16), sigs[i])
        assert got == want
        if np.unpackbits(np.frombuffer(sigs[i], np.uint8)).sum() > 0:
            assert got is not None


def test_carry_store_index_consistent_after_eviction():
    rng = np.random.default_rng(1)
    store = CarryStore(capacity=4, sim_capacity=32, stats=ServiceStats())
    for i in range(200):
        bits = rng.random(24) < 0.5
        store.put_similar(f"q{i % 3}", (8, 16), _sig(bits), carry=i)
    assert store.sim_entries == 32
    indexed = {(qd, bk, sig)
               for (qd, bk, _nb), group in store._sim_buckets.items()
               for bin_ in group.values() for sig in bin_}
    assert indexed == set(store._sim)
    assert set(store._sim_seq) == set(store._sim)
    # probes still agree with the oracle after heavy eviction churn
    for _ in range(20):
        q_sig = _sig(rng.random(24) < 0.5)
        assert store.nearest("q0", (8, 16), q_sig) == \
            store._nearest_linear("q0", (8, 16), q_sig)


def test_carry_store_linear_fallback_flag():
    store = CarryStore(capacity=4, sim_capacity=8, stats=ServiceStats(),
                       sim_index=False)
    free = np.zeros(16, bool)
    free[:8] = True
    store.put_similar("q", (8, 16), _sig(free), carry="a")
    assert store.nearest("q", (8, 16), _sig(free)) == (_sig(free), "a")


# ---------------------------------------------------------------------------
# Calibrated tier predictor + prune-latency accounting (PR 4)
# ---------------------------------------------------------------------------

def _predictor(overlap_bits=12, total=16):
    """An IMMSchedScheduler with one remembered platform state and a query
    signature overlapping it by ``overlap_bits``/``total``."""
    from repro.sched.schedulers import IMMSchedScheduler
    sched = IMMSchedScheduler()
    sched._state_index = {}
    sched._tier1_obs = {}
    stored = np.zeros(total, bool)
    stored[:overlap_bits] = True
    sched._note_state("w", free_engine_signature(stored))
    query = np.zeros(total, bool)
    query[:overlap_bits] = True
    query[overlap_bits:] = False
    query[-2:] = True                      # drifted free set, high overlap
    return sched, free_engine_signature(query)


def test_tier1_predictor_flips_on_observed_failures():
    sched, sig = _predictor()
    assert sched._predict_tier("w", sig) == 1      # prior 2/3 ≥ 0.5
    sched._note_tier1_outcome("w", sig, False)
    sched._note_tier1_outcome("w", sig, False)     # posterior 2/5 < 0.5
    assert sched._predict_tier("w", sig) == 2
    for _ in range(4):
        sched._note_tier1_outcome("w", sig, True)  # 6/9 ≥ 0.5 again
    assert sched._predict_tier("w", sig) == 1
    # unrelated workloads keep the prior
    sched._note_state("other", sig)
    assert sched._predict_tier("other", sig) == 0  # exact state stored


def test_tier1_calibration_is_bucketed_by_signature_popcount():
    sched, sig = _predictor()
    # drive this bucket's posterior below 0.5 ...
    sched._note_tier1_outcome("w", sig, False)
    sched._note_tier1_outcome("w", sig, False)
    assert sched._tier1_success_prob("w", sig) < 0.5
    # ... a very different free-set size lands in another bucket and
    # still sees the prior
    small = np.zeros(16, bool)
    small[:2] = True
    assert sched._tier1_success_prob("w", free_engine_signature(small)) \
        >= 0.5


def test_immsched_charges_prune_for_cold_swarm_decisions():
    wls = [get_workload("mobilenetv2"), get_workload("resnet50")]
    sc = fixed_scenario(wls, urgent_last=False)
    cfg = SimConfig(platform=EDGE, matcher_mode="analytic")
    r = Simulator(cfg, get_scheduler("immsched")).run(sc)
    ms = r.matcher_stats
    # cold arrivals predict Tier 2 → every swarm charge pays the fused
    # pre-prune on top, surfaced via the sched_prune_* counters
    assert ms["sched_tier2_decisions"] > 0
    assert ms["sched_prune_launches"] > 0
    assert ms["sched_prune_wall_s"] > 0
    assert ms["sched_tier1_calib_trials"] == 0     # analytic mode: no obs
    from repro.sched.metrics import pipeline_tier_rates
    rates = pipeline_tier_rates(r)
    assert rates["sched_prune_launches"] == ms["sched_prune_launches"]


def test_prune_cost_scales_with_sweeps():
    from repro.accel import CostModel
    cost = CostModel(EDGE)
    st1, se1 = cost.sched_immsched_prune(48, EDGE.engines, 16, sweeps=1)
    st8, se8 = cost.sched_immsched_prune(48, EDGE.engines, 16, sweeps=8)
    assert st8 > st1 and se8 > se1
    # the pre-prune is far below a swarm launch (it must never dominate
    # the Tier-2 charge it rides on)
    cfg = PSOConfig(num_particles=32, epochs=2, inner_steps=8)
    st_s, _ = cost.sched_immsched(48, EDGE.engines, cfg, 16)
    assert st8 < st_s


def test_service_surfaces_prune_sweeps():
    svc = MatcherService(CFG)
    q, g = _planted(0, 6, 12)
    res = svc.match(q, g, workload_key="prune/w")
    assert res.prune_sweeps >= 1
    sd = svc.stats_dict()
    assert sd["prune_problems"] == 1
    assert sd["prune_sweeps"] == res.prune_sweeps
    assert sd["avg_prune_sweeps"] == pytest.approx(res.prune_sweeps)
    # prune accounting also covers drained (batched) traffic
    svc.submit(q, g, workload_key="prune/w2")
    q2, g2 = _planted(1, 6, 12)
    svc.submit(q2, g2, workload_key="prune/w3")
    svc.drain()
    assert svc.stats_dict()["prune_problems"] >= 3


def test_tier1_calibration_recovers_from_absorbed_bucket():
    """A bucket whose posterior dropped below 0.5 is predicted Tier-2, so
    no Tier-1 predictions (and naively no observations) would ever flow
    again; verified-rebase serves of predicted-Tier-2 decisions must
    re-open it."""
    from types import SimpleNamespace
    sched, sig = _predictor()
    sched._note_tier1_outcome("w", sig, False)
    sched._note_tier1_outcome("w", sig, False)
    assert sched._predict_tier("w", sig) == 2      # absorbed (2/5 < 0.5)
    served_by_rebase = SimpleNamespace(found=True, tier=1)
    for _ in range(4):
        sched._calibrate_tier1([("w", sig, 2)], [served_by_rebase])
    assert sched._predict_tier("w", sig) == 1      # 6/9 ≥ 0.5: recovered
    # neutral evidence never moves the posterior: Tier-0 serves, cold
    # swarm serves, and skipped launches
    before = dict(sched._tier1_obs)
    sched._calibrate_tier1(
        [("w", sig, 2), ("w", sig, 2), ("w", sig, 0)],
        [SimpleNamespace(found=True, tier=0),
         SimpleNamespace(found=True, tier=2), None])
    assert dict(sched._tier1_obs) == before
