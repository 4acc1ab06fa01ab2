"""Front end (AsyncServiceFrontEnd): mean queue wait per admitted request
over the window (ms), from the service's counters: the change of
``fe_wait_s`` over the change of ``fe_admitted``. The front end counts
the wait from a request's due time to the start of its drain."""


def read(ctx):
    admitted = ctx.delta.get("fe_admitted", 0)
    if admitted <= 0:
        return None
    return 1e3 * ctx.delta["fe_wait_s"] / admitted
