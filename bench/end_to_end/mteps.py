"""Millions of non-zeros times vectors returned to clients per second,
over the whole window, its drain included."""


def read(run):
    if run.mix["kind"] == "solve" or not run.served:
        return None
    return run.nnz * len(run.served) / run.window_s / 1e6
