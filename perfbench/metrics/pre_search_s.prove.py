"""Host seconds from the call into `Solver.solve` to its first
chunk-runner call, mean over the window's undisturbed solves: host EPS
decomposition, pool padding and runner lookup, before any search runs
(`perfbench.spans`)."""

from perfbench.spans import pre_search_s


def read(run):
    return pre_search_s(run)
