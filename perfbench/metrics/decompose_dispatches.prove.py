"""Fixpoint dispatches of a solve's EPS decomposition, each read back to
the host before the next (`SolveResult.n_decompose_dispatches`), mean
over the window's undisturbed solves (`perfbench.phases`)."""

from perfbench.phases import decompose_dispatches


def read(run):
    return decompose_dispatches(run)
