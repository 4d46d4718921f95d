"""Per cent of the superstep fixpoints' lane-sweeps spent on lanes that
had already converged: 100 (1 - n_sweeps / (n_lanes x n_sweep_rounds)),
summed over the window's undisturbed solves (`perfbench.phases`)."""

from perfbench.phases import lockstep_waste


def read(run):
    return lockstep_waste(run)
