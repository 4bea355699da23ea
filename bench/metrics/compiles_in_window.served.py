"""Executables JAX built or loaded from its persistent cache inside the
window (JAX backend_compile_duration events), in a run whose window
served something; 0 when set-up's ``warm_chain`` built every shape a
cohort reaches."""


def read(run):
    return run.compiles_in_window if run.values.get("predictions") else None
