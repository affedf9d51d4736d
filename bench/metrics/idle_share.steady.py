"""Per-layer metric ``idle_share.steady``: see ``bench.readers.idle_share``."""

from bench import readers


def read(run):
    return readers.idle_share(run)
