"""Pages faulted into the resident pool from the page store per query
completed in the window: the window's delta of the counter
``store.faults`` in ``stats()["spans"]``.  A program without the counter
reads nothing."""


def read(ctx, suffix):
    before = ctx["stats_before"].get("spans") or {}
    after = ctx["stats_after"].get("spans") or {}
    if "store.faults" not in after or not ctx["completed"]:
        return None
    return (after["store.faults"] - before.get("store.faults", 0)) \
        / ctx["completed"]
