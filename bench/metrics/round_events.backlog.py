"""Per-layer metric ``round_events.backlog``: see ``bench.readers.round_events``."""

from bench import readers


def read(run):
    return readers.round_events(run)
