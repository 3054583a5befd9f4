"""The two load loops, on one thread, over a served API with ``submit``,
``tick`` and ``poll``.

* ``open_loop``: each query is sent when it is due, whatever the server
  is doing; its latency runs from when it was due.  A query not finished
  when the window closes counts at its age then.
* ``closed_loop``: ``clients`` callers, each sending its next query as
  soon as its last one is answered.

Both take the clock, the sleep and the span (a context manager factory
named by phase) as arguments, so a test can drive them on a fake clock.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field


@dataclass
class Record:
    """One query's life: when it was due, sent and answered."""
    query: list
    due: float
    sent: float | None = None
    done: float | None = None
    answer: object = None


@dataclass
class WindowStats:
    """What the loop itself counted over the window."""
    start: float = 0.0
    end: float = 0.0
    ticks: int = 0
    tick_s: float = 0.0
    lag_s: list = field(default_factory=list)
    tick_errors: list = field(default_factory=list)


def _nospan(_name):
    return contextlib.nullcontext()


class Driver:
    """Sends, ticks and collects for one served API."""

    def __init__(self, api, clock, sleep, span=_nospan):
        self.api = api
        self.clock = clock
        self.sleep = sleep
        self.span = span
        self.outstanding: dict[int, Record] = {}
        self.stats = WindowStats()

    def send(self, rec: Record) -> None:
        rec.sent = self.clock()
        with self.span("submit"):
            qid = self.api.submit(rec.query)
        self.outstanding[qid] = rec

    def collect(self) -> list[Record]:
        done = []
        with self.span("take"):
            for qid in list(self.outstanding):
                ans = self.api.poll(qid)
                if ans is not None:
                    rec = self.outstanding.pop(qid)
                    rec.done = self.clock()
                    rec.answer = ans
                    done.append(rec)
        return done

    def tick(self, counted: bool = True) -> int | None:
        """One scheduler tick; returns what the server still holds, or
        None when the tick raised."""
        t0 = self.clock()
        left = None
        try:
            with self.span("tick"):
                left = self.api.tick()
        except Exception as e:  # noqa: BLE001 - the query is retired by
            # the scheduler; it stays unanswered and fails the check
            self.stats.tick_errors.append(repr(e))
        if counted:
            self.stats.ticks += 1
            self.stats.tick_s += self.clock() - t0
        return left

    def drain(self, deadline: float) -> None:
        """Tick until every sent query is answered, the server holds none
        of them any more, or ``deadline``."""
        while self.outstanding and self.clock() < deadline:
            left = self.tick(counted=False)
            self.collect()
            if left == 0 and self.outstanding:
                break


def open_loop(driver: Driver, queries, due, seconds: float) -> list[Record]:
    """Send ``queries[i]`` at ``due[i]`` seconds after the window opens;
    return every query's record once the window closes (unanswered ones
    keep ``done`` None)."""
    clock = driver.clock
    t0 = clock()
    t_end = t0 + seconds
    driver.stats.start, driver.stats.end = t0, t_end
    recs = [Record(q, t0 + float(d)) for q, d in zip(queries, due)]
    nxt = 0
    while True:
        now = clock()
        if now >= t_end:
            break
        while nxt < len(recs) and recs[nxt].due <= now:
            driver.send(recs[nxt])
            driver.stats.lag_s.append(recs[nxt].sent - recs[nxt].due)
            nxt += 1
        driver.collect()
        if driver.outstanding:
            driver.tick()
            driver.collect()
        else:
            wake = recs[nxt].due if nxt < len(recs) else t_end
            with driver.span("wait"):
                driver.sleep(max(0.0, min(wake, t_end) - clock()))
    return recs


def finish_open(driver: Driver, recs, grace: float) -> None:
    """After the window: send what was due but never sent, and drain."""
    for rec in recs:
        if rec.sent is None:
            driver.send(rec)
    driver.drain(driver.clock() + grace)


def closed_loop(driver: Driver, next_query, clients: int,
                seconds: float, extra=lambda: 0.0) -> list[Record]:
    """``clients`` callers in a closed loop for ``seconds``, and on for
    as many seconds more as ``extra()`` reads; returns the record of every
    query sent."""
    clock = driver.clock
    t0 = clock()
    driver.stats.start = t0
    driver.stats.end = t_end = t0 + seconds
    recs = []

    def send_next():
        rec = Record(next_query(), clock())
        recs.append(rec)
        driver.send(rec)

    for _ in range(clients):
        send_next()
    while clock() < t_end + extra():
        for _ in driver.collect():
            if clock() < t_end + extra():
                send_next()
        if driver.outstanding:
            driver.tick()
    return recs


def latency_at_close(recs, t_end: float) -> list[float]:
    """Latency of every query due before ``t_end``: from due to answer,
    or its age at ``t_end`` when it was not answered by then."""
    out = []
    for r in recs:
        if r.due >= t_end:
            continue
        end = r.done if r.done is not None and r.done <= t_end else t_end
        out.append(end - r.due)
    return out


def completed_in_window(recs, t_end: float) -> int:
    return sum(1 for r in recs if r.done is not None and r.done <= t_end)
