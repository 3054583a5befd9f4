"""Work the device engine sent to its host fallback in the window (the
delta of the summed ``stats()["host_routes"]``) per query completed."""


def read(ctx, suffix):
    if not ctx["completed"]:
        return None
    n = (sum(ctx["stats_after"]["host_routes"].values())
         - sum(ctx["stats_before"]["host_routes"].values()))
    return n / ctx["completed"]
