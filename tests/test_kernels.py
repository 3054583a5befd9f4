"""Pallas kernels: shape/dtype sweeps against the ref.py pure-jnp oracles,
interpret=True on CPU (the kernel bodies execute in Python)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gap_decode.ops import gap_decode
from repro.kernels.gap_decode.ref import gap_decode_ref
from repro.kernels.bitmap_and.ops import bitmap_and
from repro.kernels.bitmap_and.ref import bitmap_and_ref
from repro.kernels.bucket_intersect.ops import bucket_intersect
from repro.kernels.bucket_intersect.ref import bucket_intersect_ref
from repro.kernels.grammar_expand.ops import grammar_expand
from repro.kernels.grammar_expand.ref import grammar_expand_ref
from repro.kernels.grammar_expand.grammar_expand import PHRASE_CAP
from repro.kernels.list_intersect.ops import list_intersect, next_geq
from repro.kernels.list_intersect.ref import (list_intersect_ref,
                                              next_geq_ref)
from repro.core.repair import repair_compress
from repro.core.jax_index import build_flat_index

INT_INF = 2**31 - 1


# -- gap_decode ----------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 7), (3, 130), (8, 512), (5, 700),
                                   (16, 1024), (2, 2000)])
def test_gap_decode_shapes(shape, rng):
    R, C = shape
    gaps = rng.integers(0, 1000, size=(R, C)).astype(np.int32)
    firsts = rng.integers(0, 100, size=(R,)).astype(np.int32)
    got = np.asarray(gap_decode(jnp.asarray(gaps), jnp.asarray(firsts)))
    ref = np.asarray(gap_decode_ref(jnp.asarray(gaps),
                                    jnp.asarray(firsts)[:, None]))
    np.testing.assert_array_equal(got, ref)


def test_gap_decode_cross_tile_carry(rng):
    """Columns > TILE_C exercise the carry scratch."""
    gaps = np.ones((8, 1537), dtype=np.int32)
    firsts = np.zeros(8, dtype=np.int32)
    got = np.asarray(gap_decode(jnp.asarray(gaps), jnp.asarray(firsts)))
    np.testing.assert_array_equal(got[0], np.arange(1, 1538))


# -- bitmap_and ------------------------------------------------------------------

@pytest.mark.parametrize("n", [128, 1024, 4096, 5000])
def test_bitmap_and_sizes(n, rng):
    a = rng.integers(0, 2**32, size=(n,), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(n,), dtype=np.uint32)
    got = np.asarray(bitmap_and(jnp.asarray(a), jnp.asarray(b)))
    ref = np.asarray(bitmap_and_ref(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(got, a & b)


def test_bitmap_and_popcount_semantics(rng):
    """The AND of two bitmaps intersects the encoded sets."""
    from repro.core.bitmaps import build_bitmap
    u = 4096
    s1 = np.sort(rng.choice(u, size=700, replace=False))
    s2 = np.sort(rng.choice(u, size=900, replace=False))
    b1 = build_bitmap(s1, u).words.view(np.uint32)
    b2 = build_bitmap(s2, u).words.view(np.uint32)
    anded = np.asarray(bitmap_and(jnp.asarray(b1), jnp.asarray(b2)))
    bits = np.unpackbits(anded.view(np.uint8), bitorder="little")
    np.testing.assert_array_equal(np.nonzero(bits[:u])[0],
                                  np.intersect1d(s1, s2))


# -- bucket_intersect -------------------------------------------------------------

@pytest.mark.parametrize("nb,cap", [(8, 128), (16, 128), (8, 256), (32, 128)])
def test_bucket_intersect_shapes(nb, cap, rng):
    def mk():
        m = np.full((nb, cap), INT_INF, dtype=np.int32)
        for r in range(nb):
            n = int(rng.integers(0, cap))
            vals = np.sort(rng.choice(10000, size=n, replace=False))
            m[r, :n] = vals + r * 10000
        return m
    a, b = mk(), mk()
    got = np.asarray(bucket_intersect(jnp.asarray(a), jnp.asarray(b)))
    ref = np.asarray(bucket_intersect_ref(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, ref)
    # semantic: per bucket, the surviving values are the set intersection
    for r in range(nb):
        av = a[r][a[r] != INT_INF]
        bv = b[r][b[r] != INT_INF]
        sv = got[r][got[r] != INT_INF]
        np.testing.assert_array_equal(np.sort(sv),
                                      np.intersect1d(av, bv))


# -- list_intersect (fused next_geq) ----------------------------------------------

@pytest.fixture(scope="module")
def li_flat(repair_result):
    return build_flat_index(repair_result)


@pytest.mark.parametrize("nq", [1, 100, 128, 300])
def test_list_intersect_next_geq_bitexact(lists, li_flat, rng, nq):
    """The fused kernel (bucket lookup + phrase-sum skip + descent in one
    pallas_call) must match the jnp engine bit-exactly, across Q paddings."""
    L = len(lists)
    lids = rng.integers(0, L, nq).astype(np.int32)
    xs = rng.integers(0, li_flat.universe + 100, nq).astype(np.int32)
    got = np.asarray(next_geq(li_flat, jnp.asarray(lids), jnp.asarray(xs),
                              interpret=True))
    ref = np.asarray(next_geq_ref(li_flat, jnp.asarray(lids),
                                  jnp.asarray(xs)))
    np.testing.assert_array_equal(got, ref)
    # and vs ground truth
    for q, (li, x) in enumerate(zip(lids, xs)):
        arr = lists[li]
        pos = np.searchsorted(arr, x)
        want = arr[pos] if pos < len(arr) else INT_INF
        assert got[q] == want


def test_list_intersect_probe_matrix(lists, li_flat, rng):
    """2-D membership filtering: INT_INF-padded probe rows against long
    lists, kernel vs jnp reference bit-exact."""
    L = len(lists)
    B, M = 6, 64
    long_ids = rng.integers(0, L, B).astype(np.int32)
    xs = np.full((B, M), INT_INF, dtype=np.int32)
    for r in range(B):
        n = int(rng.integers(1, M))
        xs[r, :n] = np.sort(rng.integers(0, li_flat.universe, n))
    got = np.asarray(list_intersect(li_flat, jnp.asarray(long_ids),
                                    jnp.asarray(xs), interpret=True))
    ref = np.asarray(list_intersect_ref(li_flat, jnp.asarray(long_ids),
                                        jnp.asarray(xs)))
    np.testing.assert_array_equal(got, ref)
    for r in range(B):
        probes = xs[r][xs[r] != INT_INF]
        kept = got[r][got[r] != INT_INF]
        np.testing.assert_array_equal(
            np.unique(kept), np.intersect1d(probes, lists[long_ids[r]]))


# -- table_gather (in-kernel lookups on the MXU) ------------------------------------

@pytest.mark.parametrize("rows", [1, 7, 128, 148, 227, 482, 512, 1100])
def test_table_gather_exact(rows):
    """One lookup per lane of a ``pack_table`` table, exact over the whole
    int32 range; an index outside the table reads 0.  1,100 rows take
    three one-hot chunks."""
    from jax.experimental import pallas as pl
    from repro.kernels.gather import pack_table, table_gather

    rng = np.random.default_rng(rows)
    size = rows * 128 - int(rng.integers(0, 128)) if rows > 1 else 100
    a = rng.integers(-2**31, 2**31, size).astype(np.int32)
    a[:5] = [INT_INF, -1, -2**31, 2**24, 2**24 + 1]
    edges = [-1, -129, size, size + 127, 0, 1, 2, 3, 4, size - 1]
    idx = np.concatenate([edges, rng.integers(0, size, 128 - len(edges))
                          ]).astype(np.int32)

    def kernel(tbl_ref, idx_ref, out_ref):
        out_ref[...] = table_gather(tbl_ref, idx_ref[...])

    got = np.asarray(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((1, idx.size), jnp.int32),
        interpret=True)(pack_table(a), jnp.asarray(idx)[None, :]))[0]
    inside = (idx >= 0) & (idx < size)
    want = np.where(inside, np.take(a, idx, mode="clip"), 0)
    np.testing.assert_array_equal(got, want)


# -- grammar_expand ---------------------------------------------------------------

def test_grammar_expand_vs_ref_and_truth(lists):
    res = repair_compress(lists, max_rules=400)
    fi = build_flat_index(res)
    left = np.asarray(fi.sym_left)
    right = np.asarray(fi.sym_right)
    sums = np.asarray(fi.sym_sum)
    lens = np.asarray(fi.sym_len)
    # pick symbols whose expansion fits PHRASE_CAP
    cand = np.nonzero(lens <= PHRASE_CAP)[0]
    syms = cand[: (cand.size // 16) * 16][:64].astype(np.int32)
    if syms.size == 0:
        pytest.skip("no symbols small enough")
    got = np.asarray(grammar_expand(
        jnp.asarray(syms), jnp.asarray(left), jnp.asarray(right),
        jnp.asarray(sums), jnp.asarray(lens), max_depth=fi.max_depth))
    ref = np.asarray(grammar_expand_ref(
        jnp.asarray(syms), jnp.asarray(left), jnp.asarray(right),
        jnp.asarray(sums), jnp.asarray(lens), max_depth=fi.max_depth,
        phrase_cap=PHRASE_CAP))
    np.testing.assert_array_equal(got, ref)
    # ground truth from the host grammar
    T = fi.num_terminals
    for w, s in enumerate(syms):
        if s < T:
            want = [int(sums[s])]
        else:
            want = [int(sums[t]) if t < T else None
                    for t in []]  # placeholder
            # expand via flat tables on host
            stack = [int(s)]
            want = []
            while stack:
                t = stack.pop()
                if left[t] < 0:
                    want.append(int(sums[t]))
                else:
                    stack.append(int(right[t]))
                    stack.append(int(left[t]))
        row = got[w][: len(want)]
        np.testing.assert_array_equal(row, want)
        assert (got[w][len(want):] == 0).all()


@pytest.mark.parametrize("dtype", [np.int32])
def test_grammar_expand_terminals_only(dtype, rng):
    """Terminals expand to themselves."""
    S = 64
    left = np.full(S, -1, dtype=np.int32)
    right = np.full(S, -1, dtype=np.int32)
    sums = np.arange(S, dtype=np.int32)
    lens = np.ones(S, dtype=np.int32)
    syms = rng.integers(0, S, size=16).astype(dtype)
    got = np.asarray(grammar_expand(
        jnp.asarray(syms), jnp.asarray(left), jnp.asarray(right),
        jnp.asarray(sums), jnp.asarray(lens), max_depth=4))
    for w, s in enumerate(syms):
        assert got[w, 0] == s
        assert (got[w, 1:] == 0).all()
