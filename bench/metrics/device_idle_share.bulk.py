"""Share of the traced window in which no operation ran on the device
(profiler trace; bench/trace.py)."""


def read(run):
    return run.trace["idle_share"] if run.trace else None
