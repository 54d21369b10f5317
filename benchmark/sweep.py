"""Find the highest arrival rate an open-loop cell sustains: run the cell
at each rate given, one process per run, and report its latencies.

    python3 benchmark/sweep.py --workload <cell> --rates 10,20,40 --seconds 20 --seed 1

Past the highest rate the system sustains the backlog grows through the
window and the tail climbs with it. The rate a cell runs at is written
into its traffic file as a number; this tool is how that number was
found, and is not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

ONE_RUN = """\
import sys
sys.path.insert(0, {root!r})
from benchmark import run

def main():
    real = run.load_cell

    def at_rate(name):
        cell = real(name)
        cell.traffic["loop"]["rate_per_s"] = {rate!r}
        return cell

    run.load_cell = at_rate
    sys.exit(run.main({argv!r}))

if __name__ == "__main__":
    main()
"""


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        script = ONE_RUN.format(root=ROOT, rate=rate, argv=[
            "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "0"])
        r = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                           capture_output=True, text=True)
        lines = r.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1])
        except (IndexError, json.JSONDecodeError):
            print(f"sweep rate={rate} rc={r.returncode} no result: "
                  f"{r.stderr[-600:]}", flush=True)
            continue
        window = [ln for ln in lines if ln.startswith("window:")]
        m = result["metrics"]
        print(f"sweep rate={rate} correct={result['correct']} "
              f"attempted={result['attempted']} failed={result['failed']} "
              f"p50_ms={m.get('p50_ms', {}).get('value')!r} "
              f"p95_ms={m.get('p95_ms', {}).get('value')!r} "
              f"{window[0] if window else ''}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
