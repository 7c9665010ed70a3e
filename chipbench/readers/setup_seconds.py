"""Process start to the window's start: load, index, warm-up, compiles."""


def read(w):
    return w.setup_s
