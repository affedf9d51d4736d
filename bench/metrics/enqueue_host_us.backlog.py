"""Per-layer metric ``enqueue_host_us.backlog``: host time of one
``SvdFleet.enqueue`` (route, place, admit), in µs."""

from bench import program_readers


def read(run):
    value = program_readers.counter_ratio("enqueue_host_ns", "enqueue_timed")
    return None if value is None else value / 1e3
