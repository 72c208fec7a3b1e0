"""Host milliseconds per solve: the solver's span less its
``solver-wait`` (the first read of the loop's result, which blocks until
the device finishes), over the window's solves."""

SOLVER_SPANS = {"pagerank": "pagerank", "power_iteration": "power-iteration",
                "conjugate_gradient": "conjugate-gradient",
                "cg": "conjugate-gradient"}


def read(run):
    if run.mix["kind"] != "solve":
        return None
    solves = run.spans.get(SOLVER_SPANS[run.mix["solver"]], [])
    waits = run.spans.get("solver-wait", [])
    if not solves or not waits:
        return None
    return (sum(solves) - sum(waits)) / len(solves) * 1e3
