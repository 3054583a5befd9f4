"""Device milliseconds of the ``list_intersect`` kernel in the traced
window per query completed there."""


def read(ctx, suffix):
    tr = ctx["trace"]
    if tr is None or not ctx["completed"] or "list_intersect" not in tr["kernels"]:
        return None
    return tr["kernels"]["list_intersect"] * 1e3 / ctx["completed"]
