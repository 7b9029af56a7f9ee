"""Share of the device-busy time inside the run spans that adi's column
sweep takes, in per cent: the summed durations of the ops named
``adi_column_sweep`` (the trace's top ops, which start inside the window,
where every op of a ``full_run`` window lies in a run span), over the
device-busy time inside the run spans.  The rest is the row sweep and the
little around them, so it says which sweep sets the pace.  A trace with no
such op gives nothing."""

KERNEL = "adi_column_sweep"


def read(record):
    t = record.trace
    if t is None or not t.runs or t.busy_in_runs_s <= 0:
        return None
    busy = sum(s for name, s in t.top_ops
               if name.split()[0].lstrip("%").split(".")[0] == KERNEL)
    return 100.0 * busy / t.busy_in_runs_s if busy else None
