"""Least seconds the chip could take for a piece of work (``rooflines/``,
from the cell's sizes and the chip's peaks) over the device seconds its
requests really took in the traced slice, in per cent. Nothing to read
gives nothing, never 0."""


def read(w, roofline, shape):
    busy = None if w.trace is None else w.trace.busy_in_shape(shape)
    if not busy:
        return None
    least = w.roofline(roofline).least_seconds(
        w.sizes, w.config["index_itemsize"], w.peaks())
    return 100.0 * least / busy
