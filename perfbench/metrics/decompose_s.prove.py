"""Host seconds a solve spends preparing its search pool (EPS
decomposition, padding, transfer), as the program times it
(`SolveResult.decompose_s`), mean over the window's undisturbed
solves (`perfbench.phases`)."""

from perfbench.phases import decompose_s


def read(run):
    return decompose_s(run)
