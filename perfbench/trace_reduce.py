"""Reduce a profiler trace of one window to device metrics.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into flat events ``(plane, line, name, start_ns, dur_ns)``.  ``reduce``
takes those events and the window, and gives:

* ``busy_s``: per device, the union of the intervals in which an XLA op
  ran, clipped to the window; averaged over the devices that ran any;
* ``kernels``: seconds of device time per kernel, by the name table
  ``KERNEL_OPS``;
* ``device_ops``: the ops that took most device time, by short name;
* ``idle_gaps``: the longest gaps between device ops, each named by the
  innermost host span on the benchmark's thread that covers its middle.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

#: kernel -> short names of its device ops.  A TPU trace names an op by
#: its HLO text, ``%<name>.<n> = <shape> custom-call(...)``; a Pallas
#: kernel's custom call takes the name of the jitted wrapper that
#: launches it (``kernels/<kernel>/ops.py``).
KERNEL_OPS = {
    "list_intersect": ("_paged_call",),
    "page_score": ("_call",),
    "ef_next_geq": ("_ef_call",),
    "pair_count": ("_pair_count_jit",),
}

_HLO_NAME = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=")

#: host span that marks the measured window
WINDOW_SPAN = "bench.window"

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load_events(path: str) -> list[tuple]:
    """Flat ``(plane, line, name, start_ns, dur_ns)`` events of a trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals):
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def short_name(op: str) -> str:
    """``%_paged_call.1 = s32[...] custom-call(...)`` -> ``_paged_call``;
    a name that is no HLO text is kept, cut to 80 characters."""
    m = _HLO_NAME.match(op)
    return m.group(1) if m else op[:80]


def kernel_of(op: str) -> str | None:
    name = short_name(op)
    for kernel, names in KERNEL_OPS.items():
        if name in names:
            return kernel
    return None


def reduce(events, top: int = 10) -> dict | None:
    """Device metrics of the window marked by ``WINDOW_SPAN``.  Returns
    None when the trace holds no window or no device op in it."""
    spans = [e for e in events if e[2] == WINDOW_SPAN]
    if not spans:
        return None
    _, host_line, _, w_lo, w_dur = spans[0]
    w_hi = w_lo + w_dur
    per_dev = defaultdict(list)
    op_time = defaultdict(float)
    kernels = defaultdict(float)
    for plane, line, name, t0, dur in events:
        if not plane.startswith(DEVICE_PREFIX) or line != OPS_LINE:
            continue
        lo, hi = max(t0, w_lo), min(t0 + dur, w_hi)
        if hi <= lo:
            continue
        per_dev[plane].append((lo, hi))
        op_time[short_name(name)] += (hi - lo) / 1e9
        k = kernel_of(name)
        if k is not None:
            kernels[k] += (hi - lo) / 1e9
    if not per_dev:
        return None
    busy, gaps = [], []
    for ivs in per_dev.values():
        merged = _union(ivs)
        busy.append(sum(hi - lo for lo, hi in merged) / 1e9)
        edges = [w_lo] + [x for iv in merged for x in iv] + [w_hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    host = [e for e in events if e[1] == host_line and e[2] != WINDOW_SPAN
            and e[3] < w_hi and e[3] + e[4] > w_lo]
    named = []
    for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (lo + hi) / 2
        cover = [e for e in host if e[3] <= mid <= e[3] + e[4]]
        name = min(cover, key=lambda e: e[4])[2] if cover else "none"
        named.append([name, (hi - lo) / 1e9])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": w_dur / 1e9,
        "busy_s": sum(busy) / len(busy),
        "kernels": dict(kernels),
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": named,
    }
