"""Per-layer metric ``assemble_host_ms.backlog``: host time stacking and
padding a round's operands (``assemble`` spans) per flush round, in ms."""

from bench import program_readers


def read(run):
    return program_readers.self_time_per_round_ms(run, "assemble")
