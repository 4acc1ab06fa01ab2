"""Warm-restart persistence: on-disk AOT executables + snapshot codecs.

A restarted ``MatcherService`` process used to pay the full cold path on
its very first arrival — a Python-level jit trace (seconds), an XLA
compile, and a cold :class:`~repro.core.carries.CarryStore` — exactly the
unpredictable-arrival case the paper bounds scheduling latency for. This
module removes both cold components:

  * **AOT executable cache** (:class:`AOTCache`) — every single-device
    service executable (swarm match, batched match, batched revalidate)
    is exported via ``jax.export`` on its first trace and serialized to
    ``<dir>/<kind>-<shapes>-<digest>.jaxexp``. A restarted process
    deserializes the blob and calls the compiled program **without ever
    tracing Python** (the ``jit_traces`` counter stays 0). The file key
    includes :func:`repro.kernels.backend.config_digest` — resolved
    kernel suite + every ``PSOConfig`` field — plus jax version and
    platform, so a config or toolchain drift is a clean cache miss, never
    a wrong program.
  * **XLA compile cache fallback** (:func:`enable_compilation_cache`)
    — mesh-sharded executables (``build_distributed_*``) cannot be
    exported portably (the serialized module pins device counts; the
    builders mark themselves ``aot_exportable = False``); for those, and
    for the residual XLA compile of deserialized modules, JAX's
    persistent compilation cache is turned on at one fixed directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
  * **Snapshot codecs** (:func:`encode_key` / :func:`decode_key`,
    :func:`carry_leaves` / :func:`carries_from_leaves`) — the service's
    snapshot (``MatcherService.save_snapshot``) stores warm-start carries
    as flat numpy leaf dicts through
    :class:`repro.checkpoint.manager.CheckpointManager` (atomic commit,
    versioned, digest-validated); these helpers round-trip the store keys
    (tuples containing str/int/float/bytes/None) through JSON.

Environment knobs (all optional — constructor args win):

  * ``REPRO_PERSIST_DIR`` — default persistence root for services built
    without an explicit ``persist_dir``.
  * ``REPRO_AOT_CACHE=0`` — disable the executable cache (snapshots
    stay on).
  * ``REPRO_JAX_CACHE=0`` — do not touch JAX's persistent compilation
    cache config even when a persist dir is set.
  * ``JAX_COMPILATION_CACHE_DIR`` — read by JAX itself; when set, no
    code here sets the cache directory.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import export as jax_export

#: Bump when the snapshot layout changes incompatibly; restores of any
#: other version are skipped cleanly (``snapshot_stale_skipped``).
SNAPSHOT_VERSION = 1

ENV_PERSIST_DIR = "REPRO_PERSIST_DIR"
ENV_AOT_CACHE = "REPRO_AOT_CACHE"
ENV_JAX_CACHE = "REPRO_JAX_CACHE"

_AOT_SUFFIX = ".jaxexp"


def default_persist_dir() -> Optional[str]:
    """Persistence root from the environment (None = persistence off)."""
    d = os.environ.get(ENV_PERSIST_DIR, "").strip()
    return d or None


def aot_cache_enabled() -> bool:
    """False when ``REPRO_AOT_CACHE=0`` opts the process out of AOT."""
    return os.environ.get(ENV_AOT_CACHE, "1").strip() != "0"


#: The compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: one fixed directory in the checkout (listed in ``.gitignore``). The
#: directory is part of every cache key, so it is never derived from a
#: persist root, a temp name, a pid or the time.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compilation_cache() -> Optional[str]:
    """Turn on JAX's persistent XLA compilation cache; return its dir.

    Covers what ``jax.export`` cannot: the XLA compile of a deserialized
    module, and mesh-sharded executables that are never exported. When
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and the directory
    is left alone; otherwise the cache is :data:`CHECKOUT_CACHE_DIR`.
    The min-compile-time/entry-size floors are zeroed so the service's
    small revalidation programs qualify. Returns None (and changes
    nothing) under ``REPRO_JAX_CACHE=0``."""
    if os.environ.get(ENV_JAX_CACHE, "1").strip() == "0":
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# AOT executable cache
# ---------------------------------------------------------------------------

class AOTCache:
    """On-disk cache of ``jax.export``-serialized service executables.

    One file per executable key; keys are built by the service from
    (kind, shape bucket, batch class, config digest). All load/export
    failures degrade to the plain jit path — a corrupt or incompatible
    blob can slow a restart down but never break or change a result.

    ``stats`` is the owning service's ``ServiceStats``; this class bumps
    its ``aot_*`` and ``jit_traces`` counters so the zero-trace warm
    restart is assertable (``stats.jit_traces == 0``).
    """

    def __init__(self, directory: str, stats=None):
        self.dir = directory
        self.stats = stats
        os.makedirs(directory, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, key + _AOT_SUFFIX)

    def entries(self) -> List[str]:
        """Keys of every serialized executable currently on disk."""
        return sorted(n[:-len(_AOT_SUFFIX)] for n in os.listdir(self.dir)
                      if n.endswith(_AOT_SUFFIX))

    def _bump(self, field: str, by: int = 1) -> None:
        if self.stats is not None:
            setattr(self.stats, field, getattr(self.stats, field) + by)

    def load(self, key: str, build: Callable[[], Callable]
             ) -> Optional[Callable]:
        """Deserialized executable for ``key``, or None on a cache miss.

        The returned callable runs the serialized program with **no
        Python trace**. ``build`` is the lazy fallback: if a later call
        hits an input-signature mismatch (the exported module is exact
        about shapes/dtypes), the wrapper silently rebuilds the live jit
        function — counted in ``aot_call_fallbacks``/``jit_traces`` —
        instead of failing the request."""
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
            exported = jax_export.deserialize(bytearray(blob))
        except Exception:
            return None
        fallback: List[Callable] = []

        def call(*args):
            if fallback:
                return fallback[0](*args)
            try:
                return exported.call(*args)
            except Exception:
                self._bump("aot_call_fallbacks")
                self._bump("jit_traces")
                fallback.append(build())
                return fallback[0](*args)

        return call

    def wrap_exporting(self, key: str, fn: Callable) -> Callable:
        """Wrap a fresh jit function so its first call also exports it.

        The first invocation traces (counted in ``jit_traces``), exports
        the traced program with the concrete argument avals, and writes
        the serialized blob under ``key`` (atomic rename); subsequent
        calls run the exported module. Functions marked
        ``aot_exportable = False`` (the mesh builders in
        ``core/matcher.py``) and export failures fall through to plain
        jit, counted in ``aot_export_failures``."""
        if not getattr(fn, "aot_exportable", True):
            return fn
        state: List[Callable] = []

        def call(*args):
            if state:
                return state[0](*args)
            self._bump("jit_traces")
            try:
                exported = jax_export.export(fn)(*args)
                blob = exported.serialize()
            except Exception:
                self._bump("aot_export_failures")
                state.append(fn)
                return fn(*args)
            try:
                tmp = self._path(key) + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(bytes(blob))
                os.replace(tmp, self._path(key))
                self._bump("aot_exports")
            except OSError:  # pragma: no cover - disk full etc.
                pass
            state.append(exported.call)
            return exported.call(*args)

        return call


# ---------------------------------------------------------------------------
# Snapshot codecs
# ---------------------------------------------------------------------------

def encode_key(key: Any) -> Any:
    """JSON-safe encoding of a warm-store key.

    Keys are tuples nesting str/int/float/bool/None/bytes/tuples (the
    service's warm keys and the scheduler's ``(name, signature)``
    workload keys). Bytes become ``{"__b": hex}``, tuples
    ``{"__t": [...]}`` so :func:`decode_key` reconstructs the exact
    (hashable) original. Raises ``TypeError`` for anything else — the
    snapshot writer skips (and counts) such entries instead of storing a
    key that would never match again."""
    if key is None or isinstance(key, (str, int, float, bool)):
        return key
    if isinstance(key, bytes):
        return {"__b": key.hex()}
    if isinstance(key, tuple):
        return {"__t": [encode_key(k) for k in key]}
    raise TypeError(f"unsnapshotable key component: {type(key)!r}")


def decode_key(obj: Any) -> Any:
    """Inverse of :func:`encode_key`."""
    if isinstance(obj, dict):
        if "__b" in obj:
            return bytes.fromhex(obj["__b"])
        if "__t" in obj:
            return tuple(decode_key(k) for k in obj["__t"])
        raise ValueError(f"unknown key encoding: {sorted(obj)}")
    return obj


def carry_leaves(prefix: str, carries: Sequence[tuple]
                 ) -> Dict[str, np.ndarray]:
    """Flatten a list of ``(S_star, f_star, S_bar)`` carries to a flat
    ``{leaf-name: np.ndarray}`` dict (the shape CheckpointManager's
    per-leaf .npy layout wants). Leaf names are ``{prefix}.{i}.{part}``
    with ``part`` in S/f/C; entries keep their list order so restores
    preserve LRU recency.

    Carries may be device arrays (the service keeps them device-resident
    between drains): the whole batch is materialized with ONE blocking
    ``jax.device_get`` at save time — a single host sync per snapshot —
    instead of one implicit transfer per leaf."""
    out: Dict[str, Any] = {}
    for i, (s, f, c) in enumerate(carries):
        out[f"{prefix}.{i:05d}.S"] = s
        out[f"{prefix}.{i:05d}.f"] = f
        out[f"{prefix}.{i:05d}.C"] = c
    host = jax.device_get(out)
    return {k: np.asarray(v) for k, v in host.items()}


def carries_from_leaves(prefix: str, leaves: Dict[str, np.ndarray],
                        count: int) -> List[tuple]:
    """Inverse of :func:`carry_leaves` for ``count`` entries."""
    return [(leaves[f"{prefix}.{i:05d}.S"],
             leaves[f"{prefix}.{i:05d}.f"],
             leaves[f"{prefix}.{i:05d}.C"])
            for i in range(count)]
