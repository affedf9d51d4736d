"""Per-layer metric ``null_columns.backlog``: mean number of null columns
(``s <= sqrt(eps) * max(s)``) of a state a flush round retires, one
observation per (stream, round) (``null_columns`` histogram): the columns
the truncated update's null-column repair rebuilds."""

from bench import program_readers


def read(run):
    return program_readers.histogram_mean("null_columns")
