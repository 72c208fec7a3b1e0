"""Share of the HBM roofline in the traced window
(``trace_reduce.roofline_pct``)."""
import trace_reduce


def read(run):
    return trace_reduce.roofline_pct(run)
