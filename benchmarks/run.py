"""Benchmark orchestrator: one module per paper table/figure.

  python -m benchmarks.run            # all benches
  python -m benchmarks.run --only fig2,heights

A bench whose ``main()`` returns a JSON-serializable dict gets it written
to ``BENCH_<module-suffix>.json`` (e.g. ``benchmarks.bench_intersection``
-> ``BENCH_intersection.json`` with per-engine throughput) so the perf
trajectory is machine-readable across PRs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCHES = {
    "fig2": "benchmarks.bench_compression",
    "build": "benchmarks.bench_build",
    "heights": "benchmarks.bench_heights",
    "fig3": "benchmarks.bench_intersection",
    "boolean": "benchmarks.bench_boolean",
    "serve": "benchmarks.bench_serve",
    "topk": "benchmarks.bench_topk",
    "tradeoff": "benchmarks.bench_tradeoff",
    "fig4": "benchmarks.bench_tradeoff",     # legacy alias for tradeoff
    "hybrid": "benchmarks.bench_bitmap_hybrid",
    "optimize": "benchmarks.bench_optimize",
    "outofcore": "benchmarks.bench_outofcore",
    "ingest": "benchmarks.bench_ingest",
    "roofline": "benchmarks.roofline",
}


def _json_path(mod_name: str, out_dir: str) -> str:
    suffix = mod_name.rsplit(".", 1)[-1].removeprefix("bench_")
    return os.path.join(out_dir, f"BENCH_{suffix}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--json-dir", type=str, default=".",
                    help="where BENCH_*.json reports are written")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = (args.only.split(",") if args.only else list(BENCHES))
    failures = 0
    seen: set[str] = set()      # aliases map to one module; run it once
    for name in names:
        mod_name = BENCHES[name]
        if mod_name in seen:
            continue
        seen.add(mod_name)
        print(f"\n{'='*70}\n== {name}  ({mod_name})\n{'='*70}")
        t0 = time.perf_counter()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            payload = mod.main()
            if isinstance(payload, dict):
                path = _json_path(mod_name, args.json_dir)
                with open(path, "w") as f:
                    json.dump(payload, f, indent=2, sort_keys=True)
                print(f"[{name}] wrote {path}")
            print(f"[{name}] ok in {time.perf_counter()-t0:.1f}s")
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"[{name}] FAILED")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
