"""Per cent of the traced slices' idle time on the busiest chip that
falls inside the program's EPS decomposition: its ``eps.decompose``
spans, and the ``eps.dispatch`` spans of one the slice's end cut
(`perfbench.phases`).  In this cell one slice at the window's opening
holds whole proofs."""

from perfbench.phases import decompose_idle_share


def read(run):
    return decompose_idle_share(run)
