"""Device time per acknowledged batch: the union of device-operation
intervals in the traced window over the batches acknowledged in it."""


def read(obs):
    n = obs.counters.get("batches_acked")
    if not n or obs.trace.busy_ns <= 0:
        return None
    return obs.trace.busy_ns / 1e6 / n
