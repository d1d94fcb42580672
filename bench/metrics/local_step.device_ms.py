"""local_step.device_ms: device time per call of the ops under the
``local_step`` scope (every agent's forward, backward and optimizer update)."""


def read(r):
    return r.per_call_ms("local_step")
