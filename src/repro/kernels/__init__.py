"""Pallas TPU kernels for the hot spots of the Re-Pair index — seven on
the query side, one on the construction side (each: <name>.py
pallas_call + BlockSpec, ops.py jit wrapper, ref.py oracle):

* ``gap_decode``      — tiled exclusive-carry prefix sum: d-gaps -> doc ids.
* ``grammar_expand``  — positional phrase expansion via fixed-depth descent;
                        grammar tables live in VMEM (the paper's
                        "dictionary fits in RAM" insight, one level down).
* ``bucket_intersect``— domain-bucketed sorted-set intersection (the TPU
                        adaptation of [ST07] lookup: aligned buckets of two
                        lists intersect bucket-locally in VMEM).
* ``bitmap_and``      — word-wise AND + popcount for the [MC07] hybrid.
* ``list_intersect``  — the FUSED query path: phrase-sum skipping +
                        fixed-depth grammar descent in one grid-blocked
                        pallas_call over the PAGED stream (scalar-prefetch
                        page scheduling, one stream page per instance —
                        DESIGN.md §2.5); backs ``repro.engine.PallasEngine``
                        and is checked bit-exactly against the jnp engine.
* ``page_score``      — RANKED retrieval's ScoreRound (DESIGN.md §9):
                        block-max page-entry decode — one directory entry
                        per grid step, its stream page scalar-prefetched,
                        output tiled so gathers stay (TILE_B, width);
                        backs ``PallasEngine.decode_page_batch`` and is
                        checked bit-exactly against the windowed jnp
                        positional descent.
* ``ef_next_geq``     — the ADAPTIVE CODEC TIER's Elias-Fano probe path
                        (DESIGN.md §10.4): the host router runs the
                        high-bits selects (``core.ef.ef_probe_state_np``),
                        the kernel finishes the low-bits bucket search
                        over the paged packed-lows array with the same
                        scalar-prefetch page scheduling as
                        ``list_intersect``; backs
                        ``PallasEngine._ef_next_geq`` and is checked
                        bit-exactly against ``core.ef.ef_next_geq_np``.
* ``pair_count``      — the CONSTRUCTION path (DESIGN.md §3.3): tiled
                        pair histogram over the working sequence with
                        revisited-block accumulators; backs
                        ``repro.build.PallasBuilder`` and is checked
                        bit-exactly against the host pair counter.

All are checked on the CPU with interpret=True against their refs.  The
four served kernels (``list_intersect``, ``page_score``, ``ef_next_geq``,
``pair_count``) are also compiled for TPU v5e at bring-up shapes by
``tests/test_tpu_compile.py``: every block's last two dims are (8, 128)
multiples or equal to the array's (paged streams are laid out
``(num_pages, 1, PAGE)`` with the page dim squeezed), and tables that grow
with the index are looked up on the MXU: a row one-hot of at most 512
rows times the table's four byte planes, exact in int32
(``kernels.gather``), so no intermediate grows with the rule count.  A
launch whose lookups ran there counts ``gather.mxu.<kernel>`` beside
``launch.<kernel>``.  The other four have never been compiled for a chip.
"""

from collections.abc import Mapping

import jax

from .. import obs

#: prefix of the recorder's launch counters (``launch.<kernel>``)
LAUNCH_PREFIX = "launch."
#: prefix of the counters of launches whose table lookups ran on the MXU
#: (``gather.mxu.<kernel>``, ``kernels.gather.table_gather``)
MXU_PREFIX = "gather.mxu."


class _Launches(Mapping):
    """Kernel launches in this process by kernel name: a view of the
    recorder's ``launch.<name>`` counters (``repro.obs``), which the ops
    wrappers add to.  A launch in interpret mode counts as
    ``"<name>[interpret]"``, so a run can show that each kernel ran, and
    ran compiled.  A name that never launched reads 0."""

    def __getitem__(self, name: str) -> int:
        return obs.counter(LAUNCH_PREFIX + name)

    def __contains__(self, name) -> bool:
        return self[name] > 0

    def __iter__(self):
        n = len(LAUNCH_PREFIX)
        return iter([k[n:] for k in obs.RECORDER.counters(LAUNCH_PREFIX)])

    def __len__(self) -> int:
        return len(obs.RECORDER.counters(LAUNCH_PREFIX))


LAUNCHES = _Launches()


def count_launch(name: str, interpret: bool, mxu: bool = False) -> None:
    """One launch of kernel ``name``; ``mxu`` when its table lookups run
    on the MXU.  Interpret-mode launches count as ``<name>[interpret]``."""
    name += "[interpret]" if interpret else ""
    obs.count(LAUNCH_PREFIX + name)
    if mxu:
        obs.count(MXU_PREFIX + name)


def should_interpret() -> bool:
    """Shared interpret-mode auto-select: compiled on TPU, interpreter
    everywhere else.  Every kernel ops wrapper defaults to this."""
    return jax.default_backend() != "tpu"
