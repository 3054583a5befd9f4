"""Fixtures shared by the benchmark's CPU tests.

``test_harness.py`` plants a fault in each cell through ``FAULTS``, a
table keyed by cell name.  A cell added to ``BENCHMARK.json`` after the
table takes a planter by its query kind, so the check for a fault covers
every cell."""

from __future__ import annotations

import numpy as np
import pytest

INT_INF = np.iinfo(np.int32).max


def alter_finite_probes(srv):
    """A fault: every probe value short of ``INT_INF`` that the device
    engine produces comes back off by one.  ``INT_INF`` ("no such
    element") stays, since one more wraps it negative and a query that
    never ends would only show after the harness's graces."""
    eng = srv.engine
    probe = eng.dispatch_round

    def altered(*a, **k):
        v = np.asarray(probe(*a, **k))
        return np.where(v < INT_INF, v + 1, v)
    eng.dispatch_round = altered


@pytest.fixture(autouse=True)
def fault_for_every_cell(request, monkeypatch):
    faults = getattr(request.module, "FAULTS", None)
    if faults is None:
        return
    mod = request.module
    by_kind = {"and": alter_finite_probes, "topk": mod.alter_decodes}
    for cell in mod.CELLS:
        if cell not in faults:
            kind = mod.harness.cell_spec(mod.SPEC, cell)[2]["kind"]
            monkeypatch.setitem(faults, cell, by_kind[kind])
