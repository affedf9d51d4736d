"""Per-layer metric ``queue_wait_ms.backlog``: mean time from
``SvdService.enqueue`` to the dispatch of the round that took the event
(``queue_wait_us`` histogram), in ms."""

from bench import program_readers


def read(run):
    value = program_readers.histogram_mean("queue_wait_us")
    return None if value is None else value / 1e3
