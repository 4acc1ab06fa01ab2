"""Buffer-donation capability probes.

This module hosts the **buffer-donation capability probes** the
service's device-resident drain pipeline gates on. Donation
(``jax.jit(..., donate_argnums=...)``) is a documented API but its
*effect* varies by backend and release: some platforms silently ignore
donation (with a warning), and a ``jax.export`` round trip may or may
not preserve the input/output aliasing. Rather than pinning behaviour to
version numbers, :func:`donation_supported` and
:func:`export_preserves_donation` each run a one-shot empirical probe
(a tiny jit on this process's default backend) and cache the verdict, so
callers — and tests — can skip cleanly where the toolchain degrades.
``requirements-dev.txt`` pins the JAX lower bound where the probes are
meaningful at all (donate_argnums + ``jax.export`` interop).
"""
from __future__ import annotations

import functools
import warnings

import numpy as np


def _probe_donation(call_through_export: bool) -> bool:
    """Shared probe body: donate a buffer into a tiny jit (optionally
    round-tripped through ``jax.export`` serialize/deserialize) and
    report whether the input buffer was actually consumed."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x + jnp.float32(1.0), donate_argnums=(0,))
    x = jax.device_put(np.ones((8,), np.float32))
    with warnings.catch_warnings():
        # platforms that ignore donation warn about unused donations;
        # the probe's verdict is the deletion check, not the warning
        warnings.simplefilter("ignore")
        if call_through_export:
            from jax import export as jax_export
            exported = jax_export.export(fn)(x)
            rebuilt = jax_export.deserialize(
                bytearray(exported.serialize()))
            y = rebuilt.call(x)
        else:
            y = fn(x)
        jax.block_until_ready(y)
    deleted = getattr(x, "is_deleted", None)
    return bool(deleted()) if callable(deleted) else False


@functools.lru_cache(maxsize=None)
def donation_supported() -> bool:
    """True when ``donate_argnums`` actually consumes input buffers on
    this process's default backend (probed once, cached). False means
    donation is a silent no-op here — the service then skips threading
    donation through its executables, losing only the in-place-update
    memory saving, never correctness."""
    try:
        return _probe_donation(call_through_export=False)
    except Exception:  # pragma: no cover - exotic backends/builds
        return False


@functools.lru_cache(maxsize=None)
def export_preserves_donation() -> bool:
    """True when a ``jax.export`` serialize → deserialize → call round
    trip keeps the donated-input aliasing of the original jit (probed
    once, cached). When False, AOT-cached executables run correctly but
    without the in-place carry update — the service warns loudly instead
    of silently losing the memory benefit across restarts."""
    try:
        return _probe_donation(call_through_export=True)
    except Exception:  # pragma: no cover - export-less jax builds
        return False
