"""Conjunctive boolean queries: the answer is the docs that hold every
term, compared exactly with the intersection of the raw lists."""

from __future__ import annotations

import numpy as np

import reference


def submit(srv, terms, mix: dict) -> int:
    return srv.submit(" AND ".join(str(t) for t in terms))


def prime(engine, old) -> None:
    """Nothing of the engine is kind-specific."""


def check(cfg: dict, mix: dict, lists, num_docs: int, recs,
          control: bool) -> dict:
    """``wrong_answers``: answers that differ from the reference (limit
    0).  ``control`` puts the reference's control in the program's
    place."""
    wrong = 0
    for r in recs:
        if r.answer is None:
            continue
        got = (reference.and_control(lists, r.query) if control
               else np.asarray(r.answer, np.int64))
        wrong += not np.array_equal(got,
                                    reference.and_reference(lists, r.query))
    return {"wrong_answers": {"value": wrong, "limit": 0}}
