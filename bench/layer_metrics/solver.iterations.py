"""Mean iterations per solve of the window, as the solver reports them."""


def read(run):
    solves = [r.solve for r in run.served if r.solve is not None]
    if not solves:
        return None
    return sum(s.iterations for s in solves) / len(solves)
