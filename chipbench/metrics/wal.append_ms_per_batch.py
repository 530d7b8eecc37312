"""Host time per acknowledged batch appending to the write-ahead log: the
self time of the program's ``wal.append`` spans (encode, write, flush;
the fsync inside is ``wal.fsync``'s) over its ``durable.commit`` spans.
None where the program records no such spans."""


def read(obs):
    try:
        from repro.launch.trace import self_times, spans
    except ImportError:
        return None
    recs = spans()
    n = sum(sp.name == "durable.commit" for sp in recs)
    if not n:
        return None
    own = self_times(recs)
    return sum(own[sp.id] for sp in recs if sp.name == "wal.append") / 1e6 / n
