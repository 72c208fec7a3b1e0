"""Mean of the service's ``queue-wait`` spans: from a request's submit to
the moment the dispatcher takes it off the queue."""


def read(run):
    waits = run.spans.get("queue-wait", [])
    if run.mix["kind"] == "solve" or not waits:
        return None
    return sum(waits) / len(waits) * 1e3
