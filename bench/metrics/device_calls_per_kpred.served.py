"""Device recurrence calls (the engine's ``sim_device_calls``: shard
executions, escalations included) per 1,000 predictions the engine
answered inside the window."""


def read(run):
    n = run.values.get("engine_predictions")
    if not n or run.values.get("sim_device_calls") is None:
        return None
    return run.values["sim_device_calls"] * 1000.0 / n
