"""``roofline_share`` for a cell whose work is divided over several chips:
the least seconds ONE chip could take (``rooflines/``) over the number of
the configuration's chips — the same work, counted the same way, against
all their bandwidth — over the device seconds the shape's requests really
took in the traced slice (the mean over the chips), in per cent. Nothing to
read gives nothing, never 0."""

from client import load_module


def read(w, roofline, shape):
    of_one = load_module("readers", "roofline_share").read(w, roofline, shape)
    return None if of_one is None else of_one / w.config["chips"]
