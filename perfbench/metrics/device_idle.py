"""Percent of the traced window in which no op ran on the device: 100
times one minus the union of device-op intervals over the window."""


def read(ctx, suffix):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
