"""Requests per engine dispatch: the requests the service dispatched
over its ``repro.service.dispatch`` calls, up to the window's end."""


def read(run):
    n = run.values.get("dispatches")
    return run.values["dispatched_requests"] / n if n else None
