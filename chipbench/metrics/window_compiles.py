"""window_compiles (count): XLA compilations (compiles and loads from the
persistent cache) inside the window, from JAX's compile event."""


def read(run):
    return run.compiles
