"""Merged dispatches a query took, per query completed in the window:
the delta of the program's ``sched.rounds`` counter
(``stats()["spans"]``), which each query adds its rounds to when it
completes.  A program without the counter reads nothing."""


def read(ctx, suffix):
    before = ctx["stats_before"].get("spans")
    after = ctx["stats_after"].get("spans")
    if before is None or after is None or not ctx["completed"]:
        return None
    n = after.get("sched.rounds", 0) - before.get("sched.rounds", 0)
    return n / ctx["completed"]
