"""Seconds from process start to the window: imports, matrix generation
and registration, the preconditioner, loading or compiling every
program, and the clients' ramp."""


def read(run):
    return run.setup_s
