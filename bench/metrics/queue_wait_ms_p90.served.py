"""90th percentile of the time a request waited in the service's queue
(submit to cohort formation, the response's ``queue_s``), over the
responses the engine answered inside the window; cache hits never
queue."""


def read(run):
    waits = sorted(run.values.get("queue_s") or ())
    if not waits:
        return None
    return waits[max(0, -(-9 * len(waits) // 10) - 1)] * 1000.0
