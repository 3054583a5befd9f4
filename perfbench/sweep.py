"""Sweep the offered rate of an open-loop cell on the chip, to find the
highest rate the system sustains (the knee).

    python3 perfbench/sweep.py --workload gov2-web.and --seed 11 \
        --seconds 20 --rates 5,10,20,40

One set-up, then one window per rate, each with queries of its own.
Prints one JSON line per rate: offered and completed queries/s, median
and 95th percentile latency, and the answers' check.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness

    spec = harness.load_json(HERE.parent / "BENCHMARK.json")
    c = harness.Cell(spec, args.workload, args.seed, args.seconds, T_START)
    if not c.arrivals.OPEN:
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(c.mix, rate_qps=rate)
        w = c.window(args.seconds, stream=10 + i, mix=mix)
        checks = c.check(w)
        lat = sorted(w["latency"])
        print(json.dumps({
            "offered_qps": rate,
            "completed_qps": w["completed"] / args.seconds,
            "due": len(lat),
            "p50_ms": statistics.median(lat) * 1e3,
            "p95_ms": harness.percentile(lat, 95) * 1e3,
            "executables": w["executables"],
            "correct": harness.passes(checks), "checks": checks}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
