"""XLA programs compiled or loaded from the persistent cache per solve:
the ``jax-compile`` spans of the window over its solves (their
``solver-wait`` spans, one a solve)."""


def read(run):
    waits = run.spans.get("solver-wait", [])
    if run.mix["kind"] != "solve" or not waits:
        return None
    return len(run.spans.get("jax-compile", [])) / len(waits)
