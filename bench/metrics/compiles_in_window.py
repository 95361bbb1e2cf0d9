"""jit lowerings (a shape or program not compiled in set-up) inside the
measured window (scheduler and jit)."""


def read(record):
    return record["compiles_in_window"]
