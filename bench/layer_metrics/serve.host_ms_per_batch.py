"""Host milliseconds per launched batch in the service's ``coalesce`` and
``dispatch`` spans (``dispatch`` holds ``pack`` and the launch)."""


def read(run):
    dispatch = run.spans.get("dispatch", [])
    if run.mix["kind"] == "solve" or not dispatch:
        return None
    host = sum(run.spans.get("coalesce", [])) + sum(dispatch)
    return host / len(dispatch) * 1e3
