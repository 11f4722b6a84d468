"""Share of the traced query's window in which no operation ran on a
device, averaged over the cell's devices."""


def read(run):
    if run.trace is None:
        return None
    busy = sum(run.trace["busy_s"]) / len(run.trace["busy_s"])
    return 100.0 * (1.0 - busy / run.trace["window_s"])
