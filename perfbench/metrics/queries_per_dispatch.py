"""Queries completed in the window per merged dispatch of the scheduler
(the delta of ``stats()["dispatches"]``)."""


def read(ctx, suffix):
    n = ctx["stats_after"]["dispatches"] - ctx["stats_before"]["dispatches"]
    return ctx["completed"] / n if n else None
