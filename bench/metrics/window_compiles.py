"""Backend compiles inside the window, counted from JAX's own
``backend_compile_duration`` events (persistent-cache hits do not
compile)."""


def read(run):
    return run.compiles_window
