"""How far a counter of ``/metrics`` moved over the window (all its label
sets together). A counter that has no series yet has never moved."""


def read(w, counter):
    return float(sum(
        v for k, v in w.counters.items() if k.startswith(counter)
    ))
