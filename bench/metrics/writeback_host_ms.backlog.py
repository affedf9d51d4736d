"""Per-layer metric ``writeback_host_ms.backlog``: host time slicing a
round's outputs back into per-stream states (``writeback`` spans) per flush
round, in ms."""

from bench import program_readers


def read(run):
    return program_readers.self_time_per_round_ms(run, "writeback")
