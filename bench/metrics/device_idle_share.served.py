"""Share of the traced window in which no operation ran on the device
(profiler trace; bench/trace.py): the host's part of each cohort and
the clients' own pauses."""


def read(run):
    return run.trace["idle_share"] if run.trace else None
