"""Share of the traced ingest window in which no operation ran on the
device, in percent: 100 (1 - busy / window)."""


def read(obs):
    if obs.trace.busy_ns <= 0:
        return None
    return 100.0 * obs.trace.idle_share
