"""Graph500 traversed input edges of the queries answered in the window,
over the window's wall time (host clock)."""


def read(run):
    return sum(q.edges for q in run.queries) / run.window_s
