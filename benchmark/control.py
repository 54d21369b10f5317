"""The control: the reference computed in float32, put in the program's
place, must come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --requests N

For each seed it makes the cell's warm-up requests and its first ``N``
window requests at the cell's own sizes, answers them with the float32
reference as if it were the server (finalize order: warm-up, then the
window in order), and compares those answers with the float64 reference
exactly as a run compares the served ones. It prints one line per seed
with every number beside its limit, and exits non-zero if any seed's
control passed. It needs no chip; the benchmark's runs do not run it.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from benchmark.cell import load_cell  # noqa: E402
from benchmark.libraries import library_dir  # noqa: E402
from benchmark.traffic import WARMUP, WINDOW, Traffic, request_id  # noqa: E402


def control_checks(cell, seed: int, n_window: int, seconds: float,
                   workers: int | None = None) -> dict:
    """The numbers a run would compare, with the float32 reference as
    the served answers."""
    traffic = Traffic(cell.traffic, cell.config, seed)
    order = [request_id(WARMUP, k) for k in range(len(traffic.warmup_sizes()))]
    order += [request_id(WINDOW, k) for k in range(n_window)]
    lib_dir = library_dir(cell.config)
    want = check.replay(order, cell, seed, seconds, lib_dir, False, workers)
    got = check.replay(order, cell, seed, seconds, lib_dir, True, workers)
    records = [
        {"id": rid, "warmup": rid.startswith("w"), "status": 200,
         "total_lines": got[rid][0], "events": [list(e) for e in got[rid][1]]}
        for rid in order
    ]
    return check.compare(records, want, n_window, 0, cell.config["limits"])


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length an open loop's plan is drawn for "
                    "(default: run_seconds)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if args.seconds is None:
        from benchmark.cell import load_benchmark

        args.seconds = float(load_benchmark()["run_seconds"])
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        checks = control_checks(cell, seed, args.requests, args.seconds)
        ok = check.is_correct(checks)
        passed += ok
        nums = " ".join(f"{k}={v!r}(limit {lim!r})" for k, (v, lim) in checks.items())
        print(f"control {args.workload} seed={seed} correct={ok} {nums} "
              f"seconds={time.monotonic() - t!r}", flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
