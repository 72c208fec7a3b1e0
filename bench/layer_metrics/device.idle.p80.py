"""Share of the traced window with no program on the device
(``trace_reduce.idle_pct``)."""
import trace_reduce


def read(run):
    return trace_reduce.idle_pct(run)
