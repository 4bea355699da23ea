"""Set-up time: process start to the first timed request (imports,
generation, warming and, on a first run, compilation)."""


def read(run):
    return run.setup_s
