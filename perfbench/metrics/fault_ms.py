"""Host milliseconds per query completed in the window that the
out-of-core engine spent faulting pages in: the window's delta of the
self seconds of the span ``store.fault`` (eviction and the batched
``store.gather`` of one request's misses) in ``stats()["spans"]``.  A
program whose recorder has no such span reads nothing."""

SPAN = "store.fault"


def read(ctx, suffix):
    before = ctx["stats_before"].get("spans") or {}
    after = ctx["stats_after"].get("spans") or {}
    if SPAN not in after or not ctx["completed"]:
        return None
    s0 = before[SPAN]["self_s"] if SPAN in before else 0.0
    return (after[SPAN]["self_s"] - s0) * 1e3 / ctx["completed"]
