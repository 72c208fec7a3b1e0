"""80th percentile of client latency, from the time each request was due
to the time its result reached the client, over every request of the
window; a failed or refused request counts as missing the tail.  The
80th is the highest percentile with ten requests beyond it at the cell's
rate (57 requests in a 51 s window)."""
import math

import loadgen


def read(run):
    if run.mix["kind"] == "solve" or not run.window.requests:
        return None
    lat = [(r.done - r.due) * 1e3 if r.error is None else math.inf
           for r in run.window.requests]
    p = loadgen.percentile(lat, 80)
    return p if math.isfinite(p) else None
