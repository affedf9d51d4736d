"""Per-layer metric ``update_roofline.backlog``: see ``bench.readers.update_roofline``."""

from bench import readers


def read(run):
    return readers.update_roofline(run)
