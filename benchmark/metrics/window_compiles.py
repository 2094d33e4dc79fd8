"""window_compiles: backend compiles (or persistent-cache loads)
inside the measured window, from JAX's monitoring events. Should be 0."""


def read(run):
    return run.compiles
