"""setup_s: seconds from process start to the window's start: imports,
JAX and chip start-up, generating the histories, and checking each once,
which compiles or loads every program the window runs."""


def read(run):
    return run.setup_s
