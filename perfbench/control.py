"""Read the control's numbers for a cell: the reference with one
guarantee broken, put in the program's place, over the queries a window
of the cell sends, at the cell's size.

    python3 perfbench/control.py --workload msmarco-passage.top10 \
        --seeds 1,2,3 --queries 200

For each seed, prints one JSON line with the numbers the check compares
and whether the check failed them, as it has to.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness
    import loops
    import traffic

    spec = harness.load_json(HERE.parent / "BENCHMARK.json")
    _, cfg, mix = harness.cell_spec(spec, args.workload)
    failed_all = True
    lists, n = harness.collection(cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        qs = traffic.QueryStream(mix, lists, seed, 0).take(args.queries)
        recs = [loops.Record(q, 0.0, 0.0, 1.0, True) for q in qs]
        checks = harness.check(cfg, mix, lists, n, recs, control=True)
        failed = not harness.passes(checks)
        failed_all &= failed
        print(json.dumps({"seed": seed, "queries": len(qs),
                          "control_failed": failed, "checks": checks}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
