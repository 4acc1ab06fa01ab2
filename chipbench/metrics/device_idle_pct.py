"""Device (TPU): share of the traced window in which no operation ran on
the device (%), 1 - busy / window from the profiler trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
