"""Device busy time (union of operation intervals in the profiler
trace) per 1,000 predictions, over the calls the traced window holds."""


def read(run):
    if not run.trace or not run.trace["calls"]:
        return None
    n = run.trace["calls"] * run.values["batch_size"]
    return run.trace["busy_s"] * 1000.0 / (n / 1000.0)
