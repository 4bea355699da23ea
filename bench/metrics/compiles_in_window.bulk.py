"""Executables JAX built or loaded from its persistent cache inside the
window (JAX backend_compile_duration events); 0 when set-up warmed
every shape."""


def read(run):
    return run.compiles_in_window
