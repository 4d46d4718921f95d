"""Milliseconds per search superstep as the program times its
chunk-runner calls, each until ready (`SolveResult.search_s` over
`n_supersteps`), over the window's undisturbed solves
(`perfbench.phases`)."""

from perfbench.phases import chunk_superstep_ms


def read(run):
    return chunk_superstep_ms(run)
