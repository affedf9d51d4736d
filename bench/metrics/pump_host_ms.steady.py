"""Per-layer metric ``pump_host_ms.steady``: see ``bench.readers.pump_host_ms``."""

from bench import readers


def read(run):
    return readers.pump_host_ms(run)
