"""Load generator: 95th percentile of how late each request of the
window was handed to the front end after its due time (ms). A loop busy
draining makes the next requests late; a starved generator shows here
before it is read as a slow server."""
import math


def read(ctx):
    late = sorted(r.submitted - r.due for r in ctx.records
                  if r.submitted is not None and r.submitted <= ctx.end)
    if not late:
        return None
    return 1e3 * late[max(math.ceil(0.95 * len(late)) - 1, 0)]
