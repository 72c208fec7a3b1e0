"""Stream slots the encoded plan holds per non-zero (the program's own
``padded_slots`` and ``nnz``): 1 is no padding."""


def read(run):
    return run.op_padded_slots / run.op_nnz if run.op_nnz else None
