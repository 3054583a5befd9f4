"""Pipeline parallelism: numerical equivalence vs sequential execution.

The GPipe schedule needs a real multi-device mesh, so the check runs in a
subprocess with forced host devices (the main test process must keep its
single-device view — dryrun.py contract)."""

import subprocess
import sys
import textwrap

import pytest

_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.pipeline import (mlp_reference, mlp_stage_fn,
                                            pipeline_apply,
                                            stack_mlp_params)

    mesh = jax.make_mesh((4,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    L, d, B, M = 8, 16, 12, 3
    params = stack_mlp_params(jax.random.key(0), L, d)
    x = jax.random.normal(jax.random.key(1), (B, d), jnp.float32)

    want = mlp_reference(params, x)
    got = pipeline_apply(mesh, "stage", M, mlp_stage_fn, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    # gradients flow through the schedule (ppermute/psum are linear)
    def loss_pipe(p):
        return jnp.sum(pipeline_apply(mesh, "stage", M, mlp_stage_fn,
                                      p, x) ** 2)

    def loss_ref(p):
        return jnp.sum(mlp_reference(p, x) ** 2)

    g1 = jax.grad(loss_pipe)(params)
    g2 = jax.grad(loss_ref)(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    print("PIPELINE_OK")
""")


def test_gpipe_matches_sequential_fwd_and_bwd():
    # The 4-host-device XLA compile is CPU-starved on small CI boxes (the
    # tier-1 reference box has 2 cores); a timeout there is an environment
    # limitation, not a numerical regression — xfail (non-strict) instead
    # of erroring so tier-1 stays deterministic.  An actual mismatch still
    # fails loudly.
    try:
        r = subprocess.run([sys.executable, "-c", _PROG],
                           capture_output=True, text=True, timeout=600,
                           env={"PYTHONPATH": "src",
                                "PATH": "/usr/bin:/bin",
                                # the child must not load the TPU runtime:
                                # a chip belongs to one process
                                "JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        pytest.xfail("gpipe subprocess exceeded 600s "
                     "(CPU-starved multi-device compile on this box)")
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


# -- PostingsSource: the append-only versioned feed (DESIGN.md §3.4/§12) --


def test_doc_terms_pure_in_seed_and_doc_id():
    """A document is a pure function of (seed, doc_id): call order,
    collection size, and cache state must not change it — the invariant
    the mutation-log replay (segment tier) depends on."""
    import numpy as np
    from repro.data.pipeline import PostingsSource

    a = PostingsSource(base_docs=10, growth_docs=5, vocab=150, seed=9)
    b = PostingsSource(base_docs=999, growth_docs=1, vocab=150, seed=9)
    # query b out of order and after growing its cache far past a's
    b.docs_between(0, 60)
    for d in (57, 3, 31, 0, 12):
        np.testing.assert_array_equal(a.doc_terms(d), b.doc_terms(d))
        t = a.doc_terms(d)
        assert t.size > 0 and (np.diff(t) > 0).all()   # sorted unique
        assert t[-1] < 150
    # a different seed produces a different stream
    c = PostingsSource(base_docs=10, growth_docs=5, vocab=150, seed=10)
    assert any(not np.array_equal(a.doc_terms(d), c.doc_terms(d))
               for d in range(10))


def test_deltas_at_partition_the_corpus():
    """deltas_at(v) is exactly the docs_between slice the version adds;
    concatenating deltas 0..v reproduces the full corpus at v."""
    import numpy as np
    from repro.data.pipeline import PostingsSource

    src = PostingsSource(base_docs=12, growth_docs=7, vocab=120, seed=4)
    assert len(src.deltas_at(0)) == 12
    for v in (1, 2, 3):
        delta = src.deltas_at(v)
        assert len(delta) == 7
        lo = src.num_docs_at(v - 1)
        for got, want in zip(delta, src.docs_between(lo, lo + 7)):
            np.testing.assert_array_equal(got, want)
    full = src.docs_between(0, src.num_docs_at(3))
    cat = [d for v in range(4) for d in src.deltas_at(v)]
    assert len(cat) == len(full)
    for got, want in zip(cat, full):
        np.testing.assert_array_equal(got, want)


def test_lists_at_append_only_growth():
    """Snapshot v extends snapshot v-1: every term's postings at v-1 are
    a prefix of its postings at v, and the term universe only widens."""
    import numpy as np
    from repro.data.pipeline import PostingsSource

    src = PostingsSource(base_docs=40, growth_docs=25, vocab=200, seed=6)

    def by_term(version):
        docs = src.docs_between(0, src.num_docs_at(version))
        inv = {}
        for d, terms in enumerate(docs):
            for t in terms.tolist():
                inv.setdefault(int(t), []).append(d)
        return inv

    prev = by_term(0)
    lists0, n0 = src.lists_at(0)
    assert n0 == 40 and len(lists0) == len(prev)
    for v in (1, 2):
        cur = by_term(v)
        assert set(prev) <= set(cur)           # universe only widens
        for t, plist in prev.items():
            assert cur[t][:len(plist)] == plist    # strict prefix growth
        lists, n = src.lists_at(v)
        assert n == src.num_docs_at(v) and len(lists) == len(cur)
        for arr, t in zip(lists, sorted(cur)):
            np.testing.assert_array_equal(arr, np.asarray(cur[t]))
        prev = cur
