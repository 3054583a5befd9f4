"""Run one benchmark cell once on the chip and print its result line.

    python3 perfbench/run.py --workload gov2-web.and --seed 7 \
        --seconds 30 --trace 0

The cell's configuration, traffic mix and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of stdout
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device`` and, last, ``checks``: each number compared with
the reference beside its limit.  The same numbers are the last lines of
stderr.  With no TPU, or fewer chips than the cell asks for, the run
prints no result and exits 1.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    try:
        import harness
        spec = harness.load_json(HERE.parent / "BENCHMARK.json")
        out = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except Exception:  # noqa: BLE001 - any failure: no result, exit 1
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
