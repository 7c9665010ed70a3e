"""Peak bytes in use on the fullest chip, read after the window."""


def read(w):
    if w.memory_peak_bytes is None:
        return None
    return w.memory_peak_bytes / 1e9
