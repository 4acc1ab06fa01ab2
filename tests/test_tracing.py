"""Program spans and tier-program names in a profiler trace, on the CPU.

A few front-end drains (a cold Tier-2 round, then its warm Tier-0 repeat)
and one direct ``MatcherService.match`` run under ``jax.profiler.trace``;
the ``.xplane.pb`` it writes must hold the ``immsched.*`` spans with
their arguments and the named tier programs. Tracing must not change a
result."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import graphs, pso
from repro.core.matcher import (build_distributed_match,
                                build_distributed_match_batch,
                                build_distributed_revalidate_batch)
from repro.core.service import AsyncServiceFrontEnd, MatcherService

jax.config.update("jax_platform_name", "cpu")

CFG = pso.PSOConfig(num_particles=24, epochs=3, inner_steps=8)
SPANS = ("immsched.drain", "immsched.prepare", "immsched.dispatch",
         "immsched.fetch", "immsched.apply")
#: two planted problems of one bucket whose stored carries revalidate:
#: cold (Tier 2), then their exact repeats (Tier 0)
SEEDS = (101, 102)


def _planted(seed, n=6, m=12, edge_prob=0.35):
    kq, kt = jax.random.split(jax.random.PRNGKey(seed))
    q = graphs.random_dag(kq, n, edge_prob)
    return q, graphs.embed_query_in_target(kt, q, m)


def _serve(trace_dir=None):
    """Two flushed drains of the same two problems, then a direct match
    of a third; returns the results in that order and the request ids
    of each drain."""
    svc = MatcherService(CFG, batch_classes=(1, 2, 4))
    fe = AsyncServiceFrontEnd(svc, max_depth=8)
    probs = {s: _planted(s) for s in SEEDS}

    def rounds():
        out, rids = [], []
        for _ in range(2):
            ids = [fe.submit(q, g, now=0.0, key=jax.random.PRNGKey(s),
                             workload_key=("w", s))
                   for s, (q, g) in probs.items()]
            fe.flush(now=0.0)
            out += [fe.take_result(r) for r in ids]
            rids.append(ids)
        q, g = _planted(103)
        out.append(svc.match(q, g, key=jax.random.PRNGKey(103)))
        return out, rids

    if trace_dir is None:
        return rounds()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # spans and programs only
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        return rounds()


def _host_events(trace_dir):
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(ev.name, ev.start_ns, ev.end_ns,
                            dict(ev.stats) if ev.name in SPANS else {})
                           for ev in line.events]
    return events


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("trace")
    results, rids = _serve(trace_dir)
    return results, rids, _host_events(trace_dir)


def test_served_path_is_cold_then_warm(traced):
    results, _, _ = traced
    assert [r.tier for r in results] == [2, 2, 0, 0, 2]
    assert all(r.found for r in results)


def test_every_span_is_written(traced):
    names = {name for name, *_ in traced[2]}
    assert set(SPANS) <= names


def test_drain_children_carry_their_drain_number(traced):
    events = traced[2]
    drains = [(s, e, int(st["drain"])) for name, s, e, st in events
              if name == "immsched.drain"]
    assert [d for *_, d in drains] == [1, 2]
    for name, s, e, st in events:
        if name in ("immsched.dispatch", "immsched.fetch", "immsched.apply"):
            # tagged exactly when inside a drain, with that drain's number
            assert [d for a, b, d in drains if a <= s and e <= b] \
                == ([int(st["drain"])] if st else []), name
    tiers = sorted((int(st["drain"]), int(st["tier"]))
                   for name, _, _, st in events
                   if name == "immsched.dispatch")
    assert tiers == [(1, 2), (2, 0)]


def test_prepare_spans_carry_request_ids(traced):
    _, rids, events = traced
    drains = {int(st["drain"]): (s, e) for name, s, e, st in events
              if name == "immsched.drain"}
    prepares = {int(st["rid"]): (int(st["drain"]), s, e)
                for name, s, e, st in events
                if name == "immsched.prepare" and st}
    assert sorted(prepares) == sorted(r for ids in rids for r in ids)
    for number, ids in enumerate(rids, start=1):
        a, b = drains[number]
        for rid in ids:
            d, s, e = prepares[rid]
            assert d == number and a <= s and e <= b


def test_tier_programs_are_named(traced):
    names = {name for name, *_ in traced[2]}
    assert "PjitFunction(immsched_swarm_batch)" in names
    assert "PjitFunction(immsched_revalidate)" in names
    assert "PjitFunction(immsched_swarm)" in names


def test_direct_match_keeps_its_spans_untagged(traced):
    """Outside a front-end drain the spans carry no drain number (and a
    prepare span no request id)."""
    events = traced[2]
    drains = [(s, e) for name, s, e, _ in events if name == "immsched.drain"]
    outside = [(name, st) for name, s, e, st in events
               if name in SPANS and name != "immsched.drain"
               and not any(a <= s and e <= b for a, b in drains)]
    assert sorted(name for name, _ in outside) == ["immsched.fetch",
                                                   "immsched.prepare"]
    assert all(not st for _, st in outside)


def test_tracing_leaves_results_unchanged(traced):
    plain, _ = _serve()
    for a, b in zip(traced[0], plain):
        assert (a.tier, a.found, a.epochs_run) == (b.tier, b.found,
                                                   b.epochs_run)
        assert a.f_star == b.f_star
        np.testing.assert_array_equal(a.mapping, b.mapping)


def test_mesh_builders_carry_the_tier_program_names():
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    n, m = 8, 16
    sds = jax.ShapeDtypeStruct
    carry = (sds((n, m), np.float32), sds((), np.float32),
             sds((n, m), np.float32))
    batch = tuple(sds((1,) + c.shape, c.dtype) for c in carry)
    key = sds((1, 2), np.uint32)
    Q, G, mask = (sds((n, n), np.float32), sds((m, m), np.float32),
                  sds((n, m), np.float32))
    Qb, Gb, maskb = (sds((1,) + x.shape, x.dtype) for x in (Q, G, mask))
    built = (
        (build_distributed_match((n, m), mesh, CFG),
         (key, Q, G, mask, carry), "immsched_swarm"),
        (build_distributed_match_batch((n, m), mesh, CFG, batch=1),
         (key, Qb, Gb, maskb, batch), "immsched_swarm_batch"),
        (build_distributed_revalidate_batch((n, m), mesh, CFG, batch=1),
         (Qb, Gb, maskb, batch), "immsched_revalidate"),
    )
    for fn, args, name in built:
        assert fn.__name__ == name
        text = fn.lower(*args).as_text()
        assert f"module @jit_{name}" in text, name


@pytest.mark.parametrize("kwargs", [{"pipelined": False},
                                    {"tiered": False}],
                         ids=["serial", "untiered"])
def test_serial_drains_tag_their_launches(tmp_path, kwargs):
    """The serial tier walk and the untiered drain write the same
    dispatch, fetch and apply spans, tagged with the front end's drain."""
    svc = MatcherService(CFG, batch_classes=(1, 2, 4), **kwargs)
    fe = AsyncServiceFrontEnd(svc, max_depth=8)
    q, g = _planted(SEEDS[0])
    with jax.profiler.trace(str(tmp_path)):
        rid = fe.submit(q, g, now=0.0, key=jax.random.PRNGKey(SEEDS[0]))
        fe.flush(now=0.0)
    assert fe.take_result(rid).found
    spans = {(name, st.get("drain")) for name, _, _, st
             in _host_events(tmp_path) if name in SPANS}
    assert spans == {(name, 1) for name in SPANS}
