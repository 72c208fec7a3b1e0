"""Vectors per device call the service launched in the window (its
``spmv_vectors_total`` over ``spmv_batches_total``)."""


def read(run):
    if run.mix["kind"] == "solve" or not run.calls:
        return None
    return run.vectors / run.calls
