"""Host milliseconds per query outside the program's ``run()``: the
query's ``init_state`` and the fetch of its values (harness spans)."""


def read(run):
    qs = run.queries
    return sum((q.t[1] - q.t[0]) + (q.t[3] - q.t[2]) for q in qs) \
        * 1e3 / len(qs)
