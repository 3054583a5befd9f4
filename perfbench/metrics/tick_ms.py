"""Mean milliseconds of one ``scheduler.tick()`` in the window (a span
around each tick)."""


def read(ctx, suffix):
    if not ctx["ticks"]:
        return None
    return ctx["tick_s"] / ctx["ticks"] * 1e3
