"""Migration: the requests a reclaim moved that their new replica
attached from the store (``EngineStats.kv_imports`` over the window), as
a share of every request it moved; the rest recompute their context."""


def read(r):
    moved = sum(1 for t in r.tracked
                if t.sreq is not None and t.sreq.migrations > 0)
    if moved == 0:
        return None
    return 100.0 * r.counters.get("kv_imports", 0.0) / moved
