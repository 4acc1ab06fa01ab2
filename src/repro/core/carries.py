"""The carry path: where warm-start carries live and how they reach a launch.

A carry is the controller state ``(S*, f*, S̄)`` of one solved problem:
(n, m), scalar and (n, m) float32 arrays. Only this module knows its
stored forms: :class:`CarryStore` (the exact and similarity warm-start
store), :class:`DeviceCarryPool` (device slabs of refcounted rows, with
the ``carry_assemble`` and ``carry_write_back`` programs) and
:class:`CarryPath`, which decides once per service whether a launch's
carries are assembled on the device or staged through host numpy, and
whether stored carries are pool rows or host tuples. Snapshots read
every stored form through :func:`carry_tuple`.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.accel.target_graph import signature_bits
from repro.core import pso

if TYPE_CHECKING:  # pragma: no cover - annotations only
    from repro.core.service import ServiceStats


def retain(carry) -> None:
    """Count one more holder of ``carry`` when it is a pool handle."""
    r = getattr(carry, "retain", None)
    if callable(r):
        r()


def release(carry) -> None:
    """Drop one holder of ``carry`` when it is a pool handle."""
    r = getattr(carry, "release", None)
    if callable(r):
        r()


class CarryStore:
    """Two-level warm-start store for the tiered pipeline.

    * **exact** — LRU of full content keys (workload key + shapes + a
      digest of Qp/Gp/maskp): a hit means *this exact problem* was solved
      before; its carry feeds Tier 0.
    * **similarity** — LRU keyed by ``(query digest, bucket, engine
      signature)``: entries describe *which platform state* a carry was
      produced on. ``nearest`` returns the stored carry whose free-engine
      bitmask overlaps the query's the most (ties go to the most recently
      stored), feeding Tier 1 rebases under fragmentation drift.

    ``nearest`` probes a **popcount-bucketed index**: entries of one
    (query digest, bucket) group are binned by the popcount of their
    free-engine bitmask, and bins are visited in decreasing order of the
    best overlap they could possibly hold (``min(pop, query_pop)``),
    stopping as soon as the bound cannot beat the best hit found — at
    thousands of stored platform states the probe touches a handful of
    bins instead of scanning the store. The exhaustive linear scan is
    kept as ``_nearest_linear`` (``sim_index=False`` fallback, and the
    oracle the index is property-tested against).

    Popcounts are computed ONCE on host numpy when an entry is ingested
    (``_sim_pop``) — ``put``/``nearest`` never reduce a bit vector per
    stored entry again, so no store operation can turn into a per-entry
    device sync however the bits arrive.

    The store is payload-agnostic (tests store plain ints), but it
    participates in device-carry lifetime management: any stored value
    exposing ``retain``/``release`` (the service's
    :class:`DeviceCarryPool` handles) is retained on insert and released
    when it is overwritten or evicted, so slab rows are reclaimed the
    moment no store references them.
    """

    def __init__(self, capacity: int, sim_capacity: int,
                 stats: "ServiceStats", sim_index: bool = True):
        self.capacity = max(int(capacity), 1)
        self.sim_capacity = max(int(sim_capacity), 1)
        self.stats = stats
        self.sim_index = bool(sim_index)
        self._exact: "OrderedDict[Tuple, tuple]" = OrderedDict()
        self._sim: "OrderedDict[Tuple, Tuple[np.ndarray, tuple]]" = \
            OrderedDict()
        # recency sequence per similarity key (== iteration order of
        # ``_sim``): the index's explicit most-recent-wins tiebreaker
        self._sim_seq: Dict[Tuple, int] = {}
        self._seq = 0
        # (qdigest, bucket, bit-length) -> {popcount: OrderedDict[sig]}
        self._sim_buckets: Dict[Tuple, Dict[int, "OrderedDict[bytes, None]"]] \
            = {}
        # per-entry popcount, computed once at ingest (host numpy)
        self._sim_pop: Dict[Tuple, int] = {}

    def __len__(self) -> int:
        return len(self._exact)

    @property
    def sim_entries(self) -> int:
        """Number of entries currently in the similarity store."""
        return len(self._sim)

    def clear(self) -> None:
        """Drop both stores and the derived popcount index/recency,
        releasing every device-pool carry they referenced."""
        for c in self._exact.values():
            release(c)
        for _, c in self._sim.values():
            release(c)
        self._exact.clear()
        self._sim.clear()
        self._sim_seq.clear()
        self._sim_buckets.clear()
        self._sim_pop.clear()

    # -- exact tier --------------------------------------------------------

    def get(self, key) -> Tuple[Optional[tuple], bool]:
        """Exact-store lookup → ``(carry, hit)``; refreshes LRU recency
        and counts ``warm_hits``/``warm_misses``."""
        if key in self._exact:
            self._exact.move_to_end(key)
            self.stats.warm_hits += 1
            return self._exact[key], True
        self.stats.warm_misses += 1
        return None, False

    def put(self, key, carry) -> None:
        """Store ``carry`` (a ``(S*, f*, S̄)`` tuple of (n, m)/scalar/
        (n, m) arrays, or a device-pool handle of one) under the exact
        content key, evicting LRU entries beyond ``capacity``."""
        old = self._exact.get(key)
        if old is not None and old is not carry:
            release(old)
        if old is not carry:
            retain(carry)
        self._exact[key] = carry
        while len(self._exact) > self.capacity:
            _, evicted = self._exact.popitem(last=False)
            release(evicted)
            self.stats.warm_evictions += 1

    # -- similarity tier ---------------------------------------------------

    @staticmethod
    def _bits(sig: bytes) -> np.ndarray:
        return np.asarray(signature_bits(sig))

    def put_similar(self, qdigest: str, bucket: Tuple[int, int],
                    sig: bytes, carry) -> None:
        """Store ``carry`` under the similarity key (query digest, shape
        bucket, free-engine signature) and index it by signature
        popcount (computed once, at ingest); refreshes recency for
        most-recent-wins ``nearest`` tiebreaks."""
        key = (qdigest, bucket, sig)
        bits = self._bits(sig)
        prev = self._sim.get(key)
        fresh = prev is None
        if not fresh and prev[1] is not carry:
            release(prev[1])
        if fresh or prev[1] is not carry:
            retain(carry)
        self._sim[key] = (bits, carry)
        self._sim.move_to_end(key)
        self._seq += 1
        self._sim_seq[key] = self._seq
        if fresh:
            pc = int(bits.sum())
            self._sim_pop[key] = pc
            group = self._sim_buckets.setdefault(
                (qdigest, bucket, bits.shape[0]), {})
            group.setdefault(pc, OrderedDict())[sig] = None
        while len(self._sim) > self.sim_capacity:
            old_key, (old_bits, old_carry) = self._sim.popitem(last=False)
            self._drop_sim_key(old_key, old_bits)
            release(old_carry)
            self.stats.sim_evictions += 1

    def _drop_sim_key(self, key: Tuple, bits: np.ndarray) -> None:
        """Remove an evicted similarity entry from the popcount index
        (``bits``: the entry's already-unpacked bit vector; the entry's
        popcount comes from the ingest-time cache, not a recount)."""
        qd, bk, sig = key
        self._sim_seq.pop(key, None)
        pc = self._sim_pop.pop(key, None)
        gkey = (qd, bk, bits.shape[0])
        group = self._sim_buckets.get(gkey)
        if group is None:
            return
        if pc is None:  # pragma: no cover - pre-index entries
            pc = int(bits.sum())
        bin_ = group.get(pc)
        if bin_ is not None:
            bin_.pop(sig, None)
            if not bin_:
                del group[pc]
        if not group:
            del self._sim_buckets[gkey]

    def nearest(self, qdigest: str, bucket: Tuple[int, int], sig: bytes,
                exclude_sig: Optional[bytes] = None
                ) -> Optional[Tuple[bytes, tuple]]:
        """Stored carry of the platform state nearest to ``sig``.

        Nearest = max popcount of the AND of the free-engine bitmasks;
        ties broken toward the smaller symmetric difference, then toward
        the most recently stored entry. Returns ``(stored_sig, carry)``
        or None when no same-workload entry overlaps at all. Served from
        the popcount-bucketed index (identical results to
        ``_nearest_linear`` — property-tested) unless ``sim_index`` is
        off.
        """
        if not self.sim_index:
            return self._nearest_linear(qdigest, bucket, sig, exclude_sig)
        bits = self._bits(sig)
        qpop = int(bits.sum())
        group = self._sim_buckets.get((qdigest, bucket, bits.shape[0]))
        if not group or qpop == 0:
            return None

        def upper_bound(pc: int) -> Tuple[int, int]:
            # best (overlap, -symdiff) any popcount-pc bitmask can score
            ov = min(pc, qpop)
            return ov, -(pc + qpop - 2 * ov)

        best = None
        best_score = (0, float("-inf"), -1)     # (overlap, -symdiff, seq)
        for pc in sorted(group, key=upper_bound, reverse=True):
            ub = upper_bound(pc)
            if ub[0] <= 0 or ub < (best_score[0], best_score[1]):
                break        # bins are bound-sorted: nothing below can win
            for s in group[pc]:
                if s == exclude_sig:
                    continue
                key = (qdigest, bucket, s)
                b, carry = self._sim[key]
                overlap = int((b & bits).sum())
                if overlap <= 0:
                    continue
                score = (overlap, -int((b ^ bits).sum()),
                         self._sim_seq[key])
                if score > best_score:
                    best_score = score
                    best = (s, carry)
        return best

    # -- snapshot support --------------------------------------------------

    def export_state(self) -> Tuple[List[Tuple[Tuple, tuple]],
                                    List[Tuple[Tuple, tuple]]]:
        """Both stores as ``(exact_items, sim_items)`` key/carry lists.

        Items come out in LRU order (least recent first) so an
        ``import_state`` replay reproduces recency — evictions and
        ``nearest`` most-recent-wins tiebreaks behave identically after
        a snapshot/restore round trip. Carries are returned as stored
        (device or host arrays); the snapshot writer converts to numpy.
        """
        exact = [(k, c) for k, c in self._exact.items()]
        sim = [(k, c) for k, (_, c) in self._sim.items()]
        return exact, sim

    def import_state(self, exact_items, sim_items) -> Tuple[int, int]:
        """Replay exported items into this (fresh) store, oldest first.

        Uses the normal ``put``/``put_similar`` paths so the similarity
        popcount index and recency sequence are rebuilt from scratch —
        the snapshot never persists derived index structures, only the
        keys and carries. Returns ``(n_exact, n_sim)`` loaded. Entries
        beyond this store's capacities age out exactly as live puts
        would."""
        for k, c in exact_items:
            self.put(k, c)
        for (qdigest, bucket, sig), c in sim_items:
            self.put_similar(qdigest, bucket, sig, c)
        return len(exact_items), len(sim_items)

    def _nearest_linear(self, qdigest: str, bucket: Tuple[int, int],
                        sig: bytes, exclude_sig: Optional[bytes] = None
                        ) -> Optional[Tuple[bytes, tuple]]:
        """Exhaustive-scan fallback (and the index's test oracle)."""
        bits = self._bits(sig)
        best = None
        best_score = (0, float("-inf"))
        for (qd, bk, s), (b, carry) in self._sim.items():
            if qd != qdigest or bk != bucket or s == exclude_sig:
                continue
            if b.shape != bits.shape:
                continue
            overlap = int((b & bits).sum())
            if overlap <= 0:
                continue
            score = (overlap, -int((b ^ bits).sum()))
            if score >= best_score:     # >=: most recent wins ties
                best_score = score
                best = (s, carry)
        return best


def carry_write_back(Sb, fb, Cb, S, f, C, idx):
    """Write launch outputs into pool slabs in place. ``idx`` is (2, L)
    int32 over the L output slots: row 0 the destination slab row (the
    slab capacity, out of range, for a slot that stores nothing — the
    scatter drops it), row 1 a flag storing f* = -inf instead of the
    slot's f (a rebased Tier-2 seed)."""
    rows, seed = idx[0], idx[1] != 0
    f = jnp.where(seed, -jnp.inf, f.astype(jnp.float32))
    return (Sb.at[rows].set(S.astype(jnp.float32), mode="drop"),
            fb.at[rows].set(f, mode="drop"),
            Cb.at[rows].set(C.astype(jnp.float32), mode="drop"))


def carry_assemble(Sb, fb, Cb, idx, maskb):
    """One launch's stacked ``(S*, f*, S̄)`` inputs: slot b takes slab row
    ``idx[b]``, or the cold prior of ``maskb[b]`` where ``idx[b] < 0``.
    The result is freshly allocated, so the launch may donate it."""
    cold = idx < 0
    rows = jnp.maximum(idx, 0)
    S0, f0, C0 = pso.default_carry_batch(maskb)
    c3 = cold[:, None, None]
    return (jnp.where(c3, S0, jnp.take(Sb, rows, axis=0)),
            jnp.where(cold, f0, jnp.take(fb, rows, axis=0)),
            jnp.where(c3, C0, jnp.take(Cb, rows, axis=0)))


# one program per (slab capacity, n, m, batch class): the slabs are
# donated, so a write never doubles their footprint
_carry_write_back = jax.jit(carry_write_back, donate_argnums=(0, 1, 2))
_carry_assemble = jax.jit(carry_assemble)


class _CarryHandle:
    """Refcounted reference to one slab row of a :class:`DeviceCarryPool`.

    Stored in :class:`CarryStore` in place of a raw carry tuple: each
    store that holds the handle ``retain``\\ s it, and the row is
    returned to the pool's free list when the last reference is
    ``release``\\ d (eviction, overwrite, or ``clear``). ``materialize``
    yields the ``(S*, f*, S̄)`` view lazily — device slices, no host
    sync."""

    __slots__ = ("pool", "shape", "row", "refs")

    def __init__(self, pool: "DeviceCarryPool", shape: Tuple[int, int],
                 row: int):
        self.pool = pool
        self.shape = shape
        self.row = row
        self.refs = 0

    def retain(self) -> None:
        """Count one more store holding this row."""
        self.refs += 1

    def release(self) -> None:
        """Drop one reference; frees the slab row at zero."""
        self.refs -= 1
        if self.refs <= 0 and self.row >= 0:
            self.pool._free(self.shape, self.row)
            self.row = -1

    def materialize(self) -> tuple:
        """The stored ``(S*, f*, S̄)`` as lazy device slices."""
        return self.pool._read(self.shape, self.row)

    def __iter__(self):
        """Duck-type as the carry tuple itself: iterating a handle
        yields the materialized ``(S*, f*, S̄)`` device parts."""
        return iter(self.materialize())

    def __len__(self) -> int:
        return 3


class _LazyCarry:
    """Tuple-shaped view of a pooled carry handed out in results.

    Slicing three device arrays out of the pool costs real dispatch
    time, and most callers never look at ``result.carry`` — so Tier-0
    hits and single-device Tier-2 results hand out this view instead.
    It retains the handle (pinning the slab row even if the store evicts
    the entry later) and slices the parts out only on first access; the
    reference drops when the view is garbage-collected."""

    __slots__ = ("_handle", "_parts")

    def __init__(self, handle: "_CarryHandle"):
        handle.retain()
        self._handle = handle
        self._parts = None

    def materialize(self) -> tuple:
        if self._parts is None:
            # once sliced, the parts reference the slab *value* at this
            # moment (jax arrays are immutable), so the row pin can drop
            self._parts = self._handle.materialize()
            self._handle.release()
            self._handle = None
        return self._parts

    def __iter__(self):
        return iter(self.materialize())

    def __len__(self) -> int:
        return 3

    def __getitem__(self, i):
        return self.materialize()[i]

    def __del__(self):
        h = self._handle
        if h is not None:
            try:
                h.release()
            except Exception:  # pragma: no cover - interpreter teardown
                pass


class DeviceCarryPool:
    """Device-resident slab storage for warm-start carries.

    One growable slab triple per padded shape — ``S``: (cap, n, m),
    ``f``: (cap,), ``C``: (cap, n, m), all float32, all device-resident —
    handing out refcounted :class:`_CarryHandle` rows. Carries move
    between the slabs and a tier launch through one compiled program per
    launch in each direction:

      * ``write_back`` stores chosen output slots of a launch as fresh
        rows with ONE donated in-place program (``carry_write_back``);
        ``put`` is its one-row case, for carries that arrive alone,
      * ``assemble`` builds a launch's stacked carry input with ONE
        program (``carry_assemble``): each slot takes a row, or the cold
        prior computed from the launch's mask,
      * rows are recycled through a free list as store evictions release
        their handles.

    Slabs grow geometrically (``jnp.concatenate`` with a zero block), so
    amortized write cost stays O(row). The pool never syncs to host; the
    persistence layer materializes handles lazily at snapshot-save time.
    """

    def __init__(self, block: int = 32):
        self.block = max(int(block), 1)
        self._slabs: Dict[Tuple[int, int], dict] = {}
        self.puts = 0                # rows written
        self.writes = 0              # write programs run
        self.gathers = 0             # assembly programs run
        # steady-state drains assemble the same row sets every time;
        # caching the device index array saves a host→device transfer
        # dispatch per launch
        self._idx_cache: "OrderedDict[tuple, jax.Array]" = OrderedDict()

    def _slab(self, shape: Tuple[int, int]) -> dict:
        slab = self._slabs.get(shape)
        if slab is None:
            n, m = shape
            cap = self.block
            slab = {"S": jnp.zeros((cap, n, m), jnp.float32),
                    "f": jnp.zeros((cap,), jnp.float32),
                    "C": jnp.zeros((cap, n, m), jnp.float32),
                    "free": list(range(cap - 1, -1, -1)), "cap": cap}
            self._slabs[shape] = slab
        return slab

    def _alloc(self, shape: Tuple[int, int]) -> int:
        """Take a free row, growing the slab when none is left."""
        slab = self._slab(shape)
        if not slab["free"]:
            old = slab["cap"]
            grow = max(old, self.block)
            n, m = shape
            slab["S"] = jnp.concatenate(
                [slab["S"], jnp.zeros((grow, n, m), jnp.float32)])
            slab["f"] = jnp.concatenate(
                [slab["f"], jnp.zeros((grow,), jnp.float32)])
            slab["C"] = jnp.concatenate(
                [slab["C"], jnp.zeros((grow, n, m), jnp.float32)])
            slab["cap"] = old + grow
            slab["free"] = list(range(old + grow - 1, old - 1, -1))
        return slab["free"].pop()

    def write_back(self, S, f, C, slots: Sequence[int],
                   seeds: Sequence[int] = ()) -> List[_CarryHandle]:
        """Store output slots of one launch — ``S``: (L, n, m), ``f``:
        (L,), ``C``: (L, n, m), device or host — as fresh rows with one
        program, and return their (unretained) handles: ``slots`` with
        their own f*, then ``seeds`` with f* = -inf. The whole outputs go
        in; the program picks the slots."""
        shape = (int(S.shape[1]), int(S.shape[2]))
        pos = list(slots) + list(seeds)
        rows = [self._alloc(shape) for _ in pos]
        slab = self._slabs[shape]
        idx = np.zeros((2, S.shape[0]), np.int32)
        idx[0] = slab["cap"]
        idx[0, pos] = rows
        idx[1, list(seeds)] = 1
        slab["S"], slab["f"], slab["C"] = _carry_write_back(
            slab["S"], slab["f"], slab["C"], S, f, C, idx)
        self.puts += len(rows)
        self.writes += 1
        return [_CarryHandle(self, shape, r) for r in rows]

    def put(self, carry: tuple) -> _CarryHandle:
        """Write one ``(S*, f*, S̄)`` carry into a slab row and return its
        (unretained) handle. Accepts device or host arrays; parts are
        cast to the slab's float32."""
        S, f, C = (p[None] if isinstance(p, jax.Array)
                   else np.asarray(p, np.float32)[None] for p in carry)
        return self.write_back(S, f, C, [0])[0]

    def assemble(self, carries: Sequence[Optional[_CarryHandle]],
                 maskb) -> tuple:
        """Stacked ``(S, f, C)`` launch inputs for one batch with mask
        stack ``maskb`` (B, n, m): slot b takes the row of
        ``carries[b]``, or the cold prior of ``maskb[b]`` where it is
        None — one program, all on device. The result is freshly
        allocated, so callers may donate it to a launch."""
        slab = self._slab((int(maskb.shape[1]), int(maskb.shape[2])))
        rows = tuple(-1 if c is None else c.row for c in carries)
        idx = self._idx_cache.get(rows)
        if idx is None:
            # from a numpy array: a plain upload, no conversion program
            idx = jnp.asarray(np.asarray(rows, np.int32))
            self._idx_cache[rows] = idx
            while len(self._idx_cache) > 256:
                self._idx_cache.popitem(last=False)
        self.gathers += 1
        return _carry_assemble(slab["S"], slab["f"], slab["C"], idx, maskb)

    def _read(self, shape: Tuple[int, int], row: int) -> tuple:
        slab = self._slabs[shape]
        return (slab["S"][row], slab["f"][row], slab["C"][row])

    def _free(self, shape: Tuple[int, int], row: int) -> None:
        slab = self._slabs.get(shape)
        if slab is not None:
            slab["free"].append(row)

    @property
    def live_rows(self) -> int:
        """Rows currently referenced by at least one store entry."""
        return sum(s["cap"] - len(s["free"])
                   for s in self._slabs.values())


def carry_tuple(carry) -> tuple:
    """A stored carry as its ``(S*, f*, S̄)`` tuple: pool handles and lazy
    result views are materialized (lazy device slices, no host sync);
    plain tuples pass through."""
    if isinstance(carry, (_CarryHandle, _LazyCarry)):
        return carry.materialize()
    return carry


def result_view(carry):
    """The carry a result hands out for a stored ``carry``: a lazy view
    of its pool row (sliced only if the caller looks at it), or the
    tuple itself."""
    if isinstance(carry, _CarryHandle):
        return _LazyCarry(carry)
    return carry_tuple(carry)


class CarryPath:
    """One service's choice of carry representation, made once.

    * ``pooled`` — a single-device service stores carries as rows of
      ``pool``, written by one ``carry_write_back`` program a launch. A
      mesh service's launch outputs carry mesh shardings the slabs can't
      hold, so it stores host tuples.
    * ``assembled`` — a pooled, pipelined service builds each launch's
      carry inputs with one ``carry_assemble`` program. A mesh service
      and the serial arm (``pipelined=False``, the bitwise oracle of the
      pooled path) stage them through host numpy instead: each
      device-resident part is pulled with a blocking ``np.asarray``,
      charged to the host-sync census, and re-stacked.
    """

    def __init__(self, stats: "ServiceStats", *, single_device: bool,
                 pipelined: bool):
        self.stats = stats
        self.pooled = bool(single_device)
        self.assembled = self.pooled and bool(pipelined)
        self.pool = DeviceCarryPool()
        # per-bucket pre-finished pad carry, made once (and, pooled,
        # pinned) so padded warm batches stay all-handle
        self.pads: Dict[Tuple[int, int], object] = {}

    def launch_inputs(self, carries: Sequence, maskb: np.ndarray):
        """``(maskb, carry0)`` of one launch over the host mask stack
        ``maskb`` (B, n, m): slot b takes ``carries[b]``, or the cold
        prior of ``maskb[b]`` where it is None. Assembled, the mask is
        uploaded once and feeds the assembly program and the launch."""
        if self.assembled:
            maskb = jnp.asarray(maskb)
            return maskb, self.pool.assemble(carries, maskb)
        return maskb, self._stage([
            c if c is not None else pso.default_carry(jnp.asarray(mask))
            for c, mask in zip(carries, maskb)])

    def _stage(self, carries: List) -> tuple:
        """Host staging: stacked ``(B, ...)`` carry inputs, each part
        pulled to host and re-stacked with numpy. Those implicit
        device→host transfers are what assembly eliminates, so each
        device-resident part is charged one host sync."""
        stacked = []
        for parts in zip(*(carry_tuple(c) for c in carries)):
            host = []
            for p in parts:
                if isinstance(p, jax.Array):
                    t0 = time.perf_counter()
                    p = np.asarray(p)
                    self.stats.host_syncs += 1
                    self.stats.host_sync_wall_s += time.perf_counter() - t0
                    self.stats.host_bytes_transferred += int(p.nbytes)
                host.append(np.asarray(p))
            stacked.append(np.stack(host))
        return tuple(stacked)

    def pad(self, bucket: Tuple[int, int]):
        """The pad slots' carry for ``bucket``: identity S* and S̄ with
        f* = +inf, which clears any early-exit fitness bound, so the slot
        is pre-finished from epoch 0."""
        carry = self.pads.get(bucket)
        if carry is None:
            S_id = np.eye(*bucket, dtype=np.float32)
            carry = (S_id, np.float32(np.inf), S_id.copy())
            if self.pooled:
                carry = self.pool.put(carry)
                carry.retain()     # pinned: pads recur on every drain
            self.pads[bucket] = carry
        return carry

    def keep(self, carry: tuple, host_carry: Optional[tuple] = None):
        """The stored form of one carry that arrives alone (a single
        match's result, a restored snapshot entry): a pool row written
        from ``carry``, or, unpooled, ``host_carry`` (default
        ``carry``)."""
        if self.pooled:
            return self.pool.put(carry)
        return carry if host_carry is None else host_carry

    def keep_rebased(self, outs: dict, host: dict, hits: List[int],
                     seeds: List[int]) -> Dict[int, object]:
        """Stored forms of a revalidation launch's rebased carries, by
        slot: each of ``hits`` with its fitness as f*, each of ``seeds``
        with f* = -inf (fitness does not transfer across platform states,
        direction does). Pooled: rows from one write-back program of the
        device outputs ``outs``; else tuples of the fetched ``host``."""
        if self.pooled:
            if not (hits or seeds):
                return {}
            return dict(zip(hits + seeds, self.pool.write_back(
                outs["S_star"], outs["fitness"], outs["S_bar"], hits, seeds)))
        S, f, C = host["S_star"], host["fitness"], host["S_bar"]
        kept = {j: (S[j], f[j], C[j]) for j in hits}
        kept.update((j, (S[j], np.float32(-np.inf), C[j])) for j in seeds)
        return kept

    def keep_served(self, outs: dict, results: Sequence) -> List:
        """Stored forms of a swarm launch's carries, one per real result.
        Pooled: rows from one write-back program of the device outputs
        ``outs``, and each result's carry becomes a lazy view of its row;
        else the host carry each result was collected with."""
        if not self.pooled:
            return [r.carry for r in results]
        rows = self.pool.write_back(outs["S_star"], outs["f_star"],
                                    outs["S_bar"], range(len(results)))
        for r, h in zip(results, rows):
            r.carry = _LazyCarry(h)
        return rows
