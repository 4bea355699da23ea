"""Share of the window's completed requests answered from the service's
cross-request cache."""


def read(run):
    n = run.values.get("predictions")
    return run.values["cache_hits"] / n if n else None
