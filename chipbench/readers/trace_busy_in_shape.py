"""Device busy seconds inside one shape's requests in the traced slice
(the mean, where the slice holds several)."""


def read(w, shape):
    return None if w.trace is None else w.trace.busy_in_shape(shape)
