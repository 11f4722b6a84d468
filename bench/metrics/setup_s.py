"""Seconds from the benchmark's start to the first query: graph, engine,
compile (or compile-cache load) and warm-up."""


def read(run):
    return run.setup_s
