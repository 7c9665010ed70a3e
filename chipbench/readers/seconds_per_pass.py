"""The whole window over the whole passes it held."""


def read(w):
    return w.seconds / w.passes if w.passes else None
