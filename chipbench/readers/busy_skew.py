"""How unevenly the cell's chips were busy in the traced slice: the busiest
chip's busy seconds over the mean of all that ran an operation. 1.0 is even
(and all one chip can read); 4.0 on four chips means one did all the work.
No trace gives nothing."""

import trace_reduce


def read(w):
    if w.trace is None:
        return None
    busy = [trace_reduce.covered(b, *w.trace.slice) for b in w.trace.busy]
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean else None
