"""Per-layer metric ``flush_host_ms.backlog``: see ``bench.readers.flush_host_ms``."""

from bench import readers


def read(run):
    return readers.flush_host_ms(run)
