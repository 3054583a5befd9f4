"""Compile the four served Pallas kernels for TPU v5e, no chip attached.

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described (``topologies.get_topology_desc``) rather than attached.
Interpret mode accepts block shapes and VMEM footprints that Mosaic
refuses, so these compiles are the only check, short of a chip, that
``list_intersect``, ``page_score``, ``ef_next_geq`` and ``pair_count``
lower with ``interpret=False`` at the bring-up shapes: a 16,000-doc
collection has 4,000 lists, 41,408 rules (tables padded here to 65,536
entries) and 213 stream pages of 2,048 symbols.  Nothing runs; only
``ShapeDtypeStruct`` shapes are compiled, so no corpus is built.

The topology is described inside a fixture, never at import: only one
process may load the TPU runtime, and every test worker imports this file.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.ef_next_geq import ops as EFK
from repro.kernels.list_intersect import ops as LI
from repro.kernels.page_score import ops as PS
from repro.kernels.pair_count import ops as PC

LISTS = 4000
RULE_TABLE = 65536          # grammar tables, padded past 41,408 rules
PAGES, PAGE = 213, 2048     # the paged compressed stream
LANES = 65536               # probe lanes in one launch (512 tiles)
K_PAGES = 4                 # pages one tile reads, when its lanes cluster
STATICS = dict(max_scan=22, max_depth=15, T=2522)
GOV2_LISTS = 61618          # gov2-web's lists (61,619 ``starts`` entries)
GOV2_SYMBOLS = 29184        # and its symbol tables, 228 rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def i32(topo):
    """``i32(*shape)`` -> an int32 ShapeDtypeStruct placed on one v5e."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _table(i32, n: int):
    """A ``gather.pack_table`` table of n entries."""
    return i32(-(-n // 128), 128)


def _assert_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()


def _compile_list_intersect(i32, k_pages: int, lists: int,
                            symbols: int, rows: int = PAGES) -> None:
    """``rows`` stream pages, or slots of a resident pool."""
    tables = (_table(i32, lists + 1), _table(i32, lists),
              _table(i32, symbols), _table(i32, symbols),
              _table(i32, symbols),
              i32(rows, 1, PAGE), i32(rows, 1, PAGE))
    tiles = LI.tiles_per_launch(k_pages)
    lanes = [i32(tiles * 128) for _ in range(4)]
    _assert_kernel(LI._paged_call.lower(
        tables, i32(tiles), i32(tiles, k_pages), *lanes,
        k_pages=k_pages, interpret=False, **STATICS).compile())


@pytest.mark.parametrize("k_pages", [K_PAGES, PAGES])
def test_list_intersect_compiles(i32, k_pages):
    """The largest launch the router makes: as many tiles as fit SMEM,
    each reading a few pages or every page of the stream."""
    _compile_list_intersect(i32, k_pages, LISTS, RULE_TABLE)


def test_list_intersect_compiles_at_gov2_tables(i32):
    """The benchmark's largest tables (``gov2-web``): 482-row list
    directories, whose lookups' byte planes and row one-hots must fit
    the scoped VMEM beside 228-row symbol tables."""
    _compile_list_intersect(i32, K_PAGES, GOV2_LISTS, GOV2_SYMBOLS)


#: slots of the resident pool: ``gov2-web-ooc``'s quarter of 151 pages,
#: and a deployment's pool (4,096 pages of 2,048 symbols, 64 MiB a table)
POOL_SLOTS = [38, 4096]


@pytest.mark.parametrize("slots", POOL_SLOTS)
def test_list_intersect_compiles_over_a_resident_pool(i32, slots):
    """Out of core the kernel reads the bounded pool through the
    scalar-prefetched slot table instead of the whole stream."""
    _compile_list_intersect(i32, K_PAGES, GOV2_LISTS, GOV2_SYMBOLS,
                            rows=slots)


@pytest.mark.parametrize("slots", POOL_SLOTS)
def test_pool_update_compiles_donated(i32, slots):
    """The pool's device update at its largest bucket (every slot): it
    compiles for the chip, and its outputs alias the donated pool."""
    from repro.store.resident import _write_pool
    pool = i32(slots, 1, PAGE)
    rows = i32(slots, PAGE)
    text = _write_pool.lower(pool, pool, i32(slots), rows, rows
                             ).compile().as_text()
    assert "input_output_alias" in text


def _compile_page_score(i32, entries: int, b_pad: int = 1024) -> None:
    tables = (*(_table(i32, RULE_TABLE) for _ in range(4)),
              i32(PAGES, 1, PAGE), i32(PAGES, 1, PAGE), i32(PAGES, 1, PAGE))
    meta = [i32(entries) for _ in range(6)]
    _assert_kernel(PS._call.lower(
        tables, *meta, max_depth=STATICS["max_depth"], T=STATICS["T"],
        b_pad=b_pad, interpret=False).compile())


def test_page_score_compiles(i32):
    _compile_page_score(i32, 1024)


def test_page_score_compiles_largest_launch(i32):
    """The most entries ``page_decode`` puts in one launch: its six
    scalar-prefetched metadata arrays at their SMEM budget."""
    _compile_page_score(i32, PS.ENTRIES_PER_LAUNCH)


def test_ef_next_geq_compiles(i32):
    lo_pages = 2048      # ~1.28 M postings x a few low bits, 128 words/page
    lanes = [i32(LANES) for _ in range(11)]
    _assert_kernel(EFK._ef_call.lower(
        i32(lo_pages, 1, 128), i32(LANES // 128), *lanes, max_win=64,
        k_pages=K_PAGES, interpret=False).compile())


def test_pair_count_compiles(i32):
    # the 2,000-doc build: ~164 K stream slots, a 4,096-pair table
    n, cands = 163840, 4096
    _assert_kernel(PC._pair_count_jit.lower(
        i32(n), i32(n, dtype=jnp.bool_), i32(), i32(cands), i32(cands),
        interpret=False).compile())
