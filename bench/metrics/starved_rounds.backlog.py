"""Per-layer metric ``starved_rounds.backlog``: share (%) of rounds sealed
while no earlier round was in flight (``starved_rounds`` counter)."""

from bench import program_readers


def read(run):
    return program_readers.starved_share(run)
