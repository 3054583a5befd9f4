"""Share (%) of the window's page lookups of the resident pool that found
the page resident: the window's delta of the counter ``store.hits`` over
that of ``store.lookups`` in ``stats()["spans"]``.  A lookup is one
distinct page of one request.  A program without the counters, or a
window with no lookup, reads nothing."""


def read(ctx, suffix):
    before = ctx["stats_before"].get("spans") or {}
    after = ctx["stats_after"].get("spans") or {}
    if "store.lookups" not in after:
        return None
    lookups = after["store.lookups"] - before.get("store.lookups", 0)
    if lookups <= 0:
        return None
    hits = after.get("store.hits", 0) - before.get("store.hits", 0)
    return 100.0 * hits / lookups
