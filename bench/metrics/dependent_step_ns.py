"""Device-busy nanoseconds inside the run spans per dependent step of the
generated kernel: the runs traced, times ``n_calls`` chained calls, times
the length of one call's longest chain of dependent vector steps, which the
program counts for the cell's geometry (`StencilProgram.dependent_steps`).
Beside the chip's latency per vector operation it says whether that chain
binds the kernel.  A program that does not count its chain gives nothing.
"""


def read(record):
    from repro.runtime.pallas_codegen import STENCIL_PROGRAMS
    t, cell = record.trace, record.cell
    program = STENCIL_PROGRAMS.get(cell.config["kernel"])
    count = getattr(program, "dependent_steps", None)
    if t is None or not t.runs or t.busy_in_runs_s <= 0 or count is None:
        return None
    steps = count(cell.shape, cell.traffic["t_call"], cell.traffic["block"])
    return t.busy_in_runs_s * 1e9 / (t.runs * cell.n_calls * steps)
