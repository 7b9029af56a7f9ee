"""Device operations that started inside one run's host span, on average
over the runs of the traced window."""


def read(record):
    t = record.trace
    return t.ops_in_runs / t.runs if t is not None and t.runs else None
