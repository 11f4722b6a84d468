"""Device milliseconds of collective operations per superstep in the
traced query, on the device where they take longest.  Nothing to read
on one device."""


def read(run):
    if run.trace is None or len(run.trace["collective_s"]) < 2:
        return None
    return 1e3 * max(run.trace["collective_s"]) / run.queries[0].supersteps
