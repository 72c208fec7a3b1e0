"""99th percentile of how late the open-loop generator sent a request
after it was due, so that a starved generator is not read as a fast
server."""
import loadgen


def read(run):
    if run.mix["kind"] != "open" or not run.window.requests:
        return None
    return loadgen.percentile(
        [(r.sent - r.due) * 1e3 for r in run.window.requests], 99)
