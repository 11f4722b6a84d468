"""Supersteps per query (``RunResult.supersteps``), averaged over the
window: an exact count of the modelled machine."""


def read(run):
    return sum(q.supersteps for q in run.queries) / len(run.queries)
