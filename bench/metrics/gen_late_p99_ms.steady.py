"""Per-layer metric ``gen_late_p99_ms.steady``: see ``bench.readers.gen_late_p99_ms``."""

from bench import readers


def read(run):
    return readers.gen_late_p99_ms(run)
