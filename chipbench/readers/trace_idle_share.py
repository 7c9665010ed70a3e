"""Share of the traced slice in which no operation ran on the device."""


def read(w):
    return None if w.trace is None else 100.0 * w.trace.idle_share
