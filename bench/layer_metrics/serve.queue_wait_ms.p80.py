"""80th percentile of the service's ``queue-wait`` spans: from a
request's submit to the moment the dispatcher takes it off the queue."""
import loadgen


def read(run):
    waits = run.spans.get("queue-wait", [])
    if run.mix["kind"] == "solve" or not waits:
        return None
    return loadgen.percentile(waits, 80) * 1e3
