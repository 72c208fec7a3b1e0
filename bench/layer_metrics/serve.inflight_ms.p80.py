"""80th percentile of the service's ``inflight`` spans: from the end of a
batch's launch to the end of its device-block in the collector."""
import loadgen


def read(run):
    flights = run.spans.get("inflight", [])
    if run.mix["kind"] == "solve" or not flights:
        return None
    return loadgen.percentile(flights, 80) * 1e3
