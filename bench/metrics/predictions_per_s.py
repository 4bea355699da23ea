"""Predictions completed in whole ``predict_batch`` calls started
inside the window, over the elapsed time of those calls."""


def read(run):
    n = run.values.get("predictions")
    return n / run.values["elapsed_s"] if n else None
