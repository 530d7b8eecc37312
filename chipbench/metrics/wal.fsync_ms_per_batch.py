"""Host time per acknowledged batch in fsync of the write-ahead log: every
``wal.fsync`` span of the program (inside an append or a commit) over its
``durable.commit`` spans. None where the program records no such
spans."""


def read(obs):
    try:
        from repro.launch.trace import spans
    except ImportError:
        return None
    recs = spans()
    n = sum(sp.name == "durable.commit" for sp in recs)
    if not n:
        return None
    return sum(sp.end_ns - sp.start_ns for sp in recs
               if sp.name == "wal.fsync") / 1e6 / n
