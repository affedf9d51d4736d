"""Per-layer metric ``place_host_us.backlog``: host time of the
``jax.device_put`` in ``FleetShard._place`` per enqueued event, in µs."""

from bench import program_readers


def read(run):
    value = program_readers.counter_ratio("place_host_ns", "enqueue_timed")
    return None if value is None else value / 1e3
