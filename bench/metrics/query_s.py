"""Mean wall time of the queries answered in the window: ``init_state``
to the fetched values.  A window holds one query in today's cells, so
this is no tail (PERF.md)."""


def read(run):
    return sum(q.wall_s for q in run.queries) / len(run.queries)
