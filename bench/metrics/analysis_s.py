"""Seconds of the analysis chain (spec to plan, memo cleared) per kernel
produced in the window, from the host clock around the chain."""


def read(record):
    times = record.host.get("analysis_s")
    return sum(times) / len(times) if times else None
