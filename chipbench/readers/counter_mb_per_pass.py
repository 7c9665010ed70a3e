"""``counter_per_pass`` over counters of bytes, in megabytes (10**6) per
whole pass. No such series gives nothing."""

from client import load_module


def read(w, counters):
    moved = load_module("readers", "counter_per_pass").read(w, counters)
    return None if moved is None else moved / 1e6
