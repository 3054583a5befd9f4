"""Host milliseconds per query completed in the window that the program
spent in one layer: the window's delta of the self seconds of the
layer's spans in ``stats()["spans"]`` (the program's recorder).  A
program without the recorder reads nothing; a span that never ran in a
program that has it reads 0."""

#: layer -> the spans whose self time it is
SPANS = {
    "plan": ("sched.submit",),
    "sched": ("sched.tick",),
    "advance": ("sched.advance",),
    "lanes": ("engine.lanes",),
    "route": ("kernel.route", "kernel.launch"),
    "wait": ("device.wait",),
    "gc": ("py.gc",),
}


def self_s(spans: dict, name: str) -> float:
    s = spans.get(name)
    return s["self_s"] if s else 0.0


def read(ctx, suffix):
    before = ctx["stats_before"].get("spans")
    after = ctx["stats_after"].get("spans")
    if before is None or after is None or not ctx["completed"]:
        return None
    names = SPANS[suffix.partition(".")[0]]
    s = sum(self_s(after, n) - self_s(before, n) for n in names)
    return s * 1e3 / ctx["completed"]
