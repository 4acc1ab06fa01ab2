"""Drain (compile caches): XLA programs built inside the window, compiled
or loaded from the persistent compile cache (JAX's backend-compile
events). It counts the service's tier executables that its compile LRU
had to rebuild, and every other program the served path builds on the
fly, such as the carry pool's slab-growth updates. Set-up warms what the
traffic uses, so this should read 0."""


def read(ctx):
    return float(ctx.delta.get("xla_compiles", 0))
