"""How far the counters of ``/metrics`` whose series start with any of
``counters`` moved over the window, all together, per whole pass. No such
series (a program without the counter) gives nothing."""


def read(w, counters):
    moved = [v for k, v in w.counters.items()
             if any(k.startswith(c) for c in counters)]
    if not moved or not w.passes:
        return None
    return float(sum(moved)) / w.passes
