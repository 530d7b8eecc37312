"""Host time per acknowledged batch in the engine's own host path: the
program's ``durable.ingest`` and ``durable.commit`` spans less the
``wal.*`` spans and the ``engine.verdict_wait`` inside them (the sum of
the self times of every other span under them), over its
``durable.commit`` spans. None where the program records no such
spans."""

ROOTS = ("durable.ingest", "durable.commit")


def read(obs):
    try:
        from repro.launch.trace import self_times, spans
    except ImportError:
        return None
    recs = spans()
    n = sum(sp.name == "durable.commit" for sp in recs)
    if not n:
        return None
    by_id = {sp.id: sp for sp in recs}

    def root(sp):
        while sp.parent is not None and sp.parent in by_id:
            sp = by_id[sp.parent]
        return sp

    own = self_times(recs)
    return sum(own[sp.id] for sp in recs
               if root(sp).name in ROOTS and not sp.name.startswith("wal.")
               and sp.name != "engine.verdict_wait") / 1e6 / n
