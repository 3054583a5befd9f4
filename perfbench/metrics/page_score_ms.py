"""Device milliseconds of the ``page_score`` kernel in the traced window
per query completed there."""


def read(ctx, suffix):
    tr = ctx["trace"]
    if tr is None or not ctx["completed"] or "page_score" not in tr["kernels"]:
        return None
    return tr["kernels"]["page_score"] * 1e3 / ctx["completed"]
