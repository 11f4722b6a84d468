"""Device busy milliseconds per superstep in the traced query: the union
of the device's operation intervals, averaged over the cell's devices,
over the query's supersteps."""


def read(run):
    if run.trace is None:
        return None
    busy = sum(run.trace["busy_s"]) / len(run.trace["busy_s"])
    return 1e3 * busy / run.queries[0].supersteps
