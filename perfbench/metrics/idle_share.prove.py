"""Per cent of the traced slices in which no executable ran on the
busiest of the cell's chips (`perfbench.trace`).  In this cell one
slice at the window's opening holds whole proofs, decomposition and
search both."""


def read(run):
    return run.trace.idle_share()
