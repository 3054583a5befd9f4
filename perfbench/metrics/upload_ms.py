"""Host milliseconds per query completed in the window spent bringing
the device copy of the resident pool up to date: the window's delta of
the self seconds of the span ``store.upload`` (the dispatch of the pool's
and the slot table's updates) in ``stats()["spans"]``.  A program whose
recorder has no such span reads nothing."""

SPAN = "store.upload"


def read(ctx, suffix):
    before = ctx["stats_before"].get("spans") or {}
    after = ctx["stats_after"].get("spans") or {}
    if SPAN not in after or not ctx["completed"]:
        return None
    s0 = before[SPAN]["self_s"] if SPAN in before else 0.0
    return (after[SPAN]["self_s"] - s0) * 1e3 / ctx["completed"]
