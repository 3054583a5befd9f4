"""Closed loop: the mix's ``clients`` callers, each sending its next
query as soon as its last one is answered."""

from __future__ import annotations

import loops

#: a caller waits for its answer, so only completions are judged
OPEN = False


def run(driver, mix: dict, stream, seconds: float, seed: int,
        extra=None) -> list:
    """Run the callers for ``seconds``, and on for as many seconds more
    as ``extra()`` reads where given."""
    return loops.closed_loop(driver, lambda: stream.take(1)[0],
                             int(mix["clients"]), seconds,
                             extra=extra or (lambda: 0.0))


def finish(driver, recs, grace: float) -> None:
    driver.drain(driver.clock() + grace)


def attempted(recs, end: float) -> int:
    return len(recs)
