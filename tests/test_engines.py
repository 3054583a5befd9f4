"""Cross-backend parity: HostEngine, JnpEngine, and PallasEngine
(interpret=True) must return identical results for every engine operation,
including the edge cases — empty intersection, singleton lists, and probes
past the last element (x > last)."""

import numpy as np
import pytest

from strategies import adversarial_lists

from repro.core.jax_index import INT_INF, build_flat_index
from repro.core.repair import repair_compress
from repro.engine import ENGINES, HostEngine, JnpEngine, PallasEngine, \
    make_engine

MAX_SHORT = 64


@pytest.fixture(scope="module")
def elists(rng):
    """Randomized lists plus adversarial shapes: a singleton, a 2-element
    list at the universe edge, and a provably disjoint pair (see
    strategies.adversarial_lists)."""
    return adversarial_lists(rng)


@pytest.fixture(scope="module")
def eres(elists):
    return repair_compress(elists)


@pytest.fixture(scope="module")
def engines(eres):
    fi = build_flat_index(eres)
    return {
        "host": HostEngine(eres),
        "jnp": JnpEngine(eres, fi=fi, max_short_len=MAX_SHORT),
        "pallas": PallasEngine(eres, fi=fi, max_short_len=MAX_SHORT,
                               interpret=True),
    }


def _oracle_next_geq(lists, li, x):
    arr = lists[li]
    pos = np.searchsorted(arr, x)
    return int(arr[pos]) if pos < len(arr) else int(INT_INF)


def test_next_geq_parity(elists, eres, engines, rng):
    L = len(elists)
    u = eres.universe
    lids = rng.integers(0, L, 200).astype(np.int32)
    # probes spanning the domain INCLUDING x > last (u-1, and over-universe
    # values stay int32-safe)
    xs = rng.integers(0, u + u // 2, 200).astype(np.int32)
    # pin the edge cases
    lids[:4] = [10, 10, 11, 11]         # singleton + edge list
    xs[:4] = [0, u - 1, u - 1, 1]
    outs = {n: e.next_geq_batch(lids, xs) for n, e in engines.items()}
    for q, (li, x) in enumerate(zip(lids, xs)):
        want = _oracle_next_geq(elists, li, x)
        assert outs["host"][q] == want, f"host q{q} list{li} x{x}"
    np.testing.assert_array_equal(outs["host"], outs["jnp"])
    np.testing.assert_array_equal(outs["jnp"], outs["pallas"])


def test_member_parity(elists, eres, engines, rng):
    L = len(elists)
    lids, xs = [], []
    for li in range(L):
        lids += [li, li]
        xs += [int(elists[li][0]), int(elists[li][-1]) + 1]
    lids = np.asarray(lids, np.int32)
    xs = np.asarray(xs, np.int32)
    outs = {n: e.member_batch(lids, xs) for n, e in engines.items()}
    want = np.asarray([np.isin(x, elists[li]) for li, x in zip(lids, xs)])
    for n, got in outs.items():
        np.testing.assert_array_equal(got, want, err_msg=n)


def test_intersect_pairs_parity(elists, engines, rng):
    L = len(elists)
    pairs = [tuple(map(int, rng.choice(L, 2, replace=False)))
             for _ in range(12)]
    pairs += [(12, 13),          # empty intersection by construction
              (10, 0),           # singleton short side
              (11, 11 - 1)]      # edge list
    outs = {n: e.intersect_pairs(pairs) for n, e in engines.items()}
    for k, (a, b) in enumerate(pairs):
        oracle = np.intersect1d(elists[a], elists[b])
        for n in engines:
            np.testing.assert_array_equal(outs[n][k], oracle,
                                          err_msg=f"{n} pair {k}={a},{b}")
    # the constructed-disjoint pair really is the empty-result case
    assert outs["host"][12].size == 0


def test_intersect_multi_parity(elists, engines):
    queries = [[], [0], [10, 1], [2, 5, 8], [1, 4, 7, 9], [12, 13, 0]]
    for q in queries:
        oracle = elists[q[0]] if q else np.empty(0, np.int64)
        for t in q[1:]:
            oracle = np.intersect1d(oracle, elists[t])
        for n, e in engines.items():
            np.testing.assert_array_equal(e.intersect_multi(q),
                                          np.asarray(oracle, np.int64),
                                          err_msg=f"{n} query {q}")


def test_device_host_fallback_routes_long_shorts(eres, elists):
    """A device engine whose expansion cap is tiny must route through the
    host fallback and still be exact."""
    eng = JnpEngine(eres, max_short_len=4)
    big = sorted(range(len(elists)), key=lambda i: -len(elists[i]))[:2]
    out = eng.intersect_pairs([(big[0], big[1])])[0]
    np.testing.assert_array_equal(
        out, np.intersect1d(elists[big[0]], elists[big[1]]))
    out = eng.intersect_multi(big)
    np.testing.assert_array_equal(
        out, np.intersect1d(elists[big[0]], elists[big[1]]))


def test_host_routes_are_counted(eres, elists):
    """Pairs and k-term queries whose shortest list passes
    ``max_short_len`` go to the host by design; each is counted, and the
    scheduler's stats carry the counts."""
    from repro.serve.scheduler import QueryScheduler
    eng = JnpEngine(eres, max_short_len=4)
    big = sorted(range(len(elists)), key=lambda i: -len(elists[i]))[:3]
    small = min(range(len(elists)), key=lambda i: len(elists[i]))
    assert eng.host_routes == {"pairs": 0, "multi": 0, "decodes": 0}
    eng.intersect_pairs([(big[0], big[1]), (small, big[2])])
    assert eng.host_routes["pairs"] == (len(elists[small]) > 4) + 1
    eng.intersect_multi(big)
    assert eng.host_routes["multi"] == 1
    assert QueryScheduler(eng).stats()["host_routes"] == eng.host_routes


def test_engine_registry():
    assert set(ENGINES) == {"host", "jnp", "pallas"}
    with pytest.raises(ValueError, match="unknown engine"):
        make_engine("cuda", None)


def test_host_methods_agree(eres, elists, rng):
    """All three host sampling strategies answer identically."""
    L = len(elists)
    pairs = [tuple(map(int, rng.choice(L, 2, replace=False)))
             for _ in range(6)]
    outs = [HostEngine(eres, method=m).intersect_pairs(pairs)
            for m in ("skip", "svs", "lookup")]
    for k in range(len(pairs)):
        np.testing.assert_array_equal(outs[0][k], outs[1][k])
        np.testing.assert_array_equal(outs[1][k], outs[2][k])
