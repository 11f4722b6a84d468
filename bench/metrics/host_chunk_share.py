"""Share of the queries' wall time that the host spends dispatching
chunks and accounting their stats, from the program's chunk spans
(``observer=`` of ``run()``)."""


def read(run):
    qs = [q for q in run.queries if q.chunks]
    if not qs:
        return None
    host = sum(d + a for q in qs for d, _, a, _ in q.chunks)
    return 100.0 * host / sum(q.wall_s for q in qs)
