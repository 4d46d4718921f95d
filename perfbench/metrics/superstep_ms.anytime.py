"""Milliseconds per search superstep: host time of the chunk-runner
calls, each until its result is ready, over the supersteps the same
solves ran (`perfbench.spans`).  A runner call is one executable of
up to 256 supersteps, so dispatch adds well under a millisecond to
each call."""

from perfbench.spans import superstep_ms


def read(run):
    return superstep_ms(run)
