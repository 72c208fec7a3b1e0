"""Wall time of the window's solves over their count; the window ends at
the first solve to finish after its length, so it holds whole solves."""


def read(run):
    if run.mix["kind"] != "solve" or not run.served:
        return None
    return run.window_s / len(run.served)
