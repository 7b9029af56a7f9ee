"""Seconds of the Pallas codegen plus JAX's lowering and compilation per
kernel produced in the window, from the host clock around them."""


def read(record):
    times = record.host.get("lower_compile_s")
    return sum(times) / len(times) if times else None
