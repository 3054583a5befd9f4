"""Seconds the host Re-Pair builder took over the collection (a span
around ``build_grammar`` in set-up)."""


def read(ctx, suffix):
    return ctx["build_s"]
