"""Share of the traced window in which no operation ran on the device, in
per cent: 100 (1 - busy / window), busy the union of the op intervals."""


def read(record):
    t = record.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
