#!/usr/bin/env python3
"""Run one cell several times, each run a process of its own as a check
runs it, and print the spread of every metric the runs report.

    python3 benchmarks/chip/spread.py --workload yi6b.train_4k \
        --seeds 11 12 13 14 15 16 --sets 2 --seconds 10 [--trace 0]

``--sets 2`` runs the seeds twice over, set after set.  A spread is the
distance between the first and third quartiles (``statistics.quantiles``,
n=4) as a share of the median.  Every result line, with its set and seed,
goes to ``<out>/<workload>.jsonl`` (``--out``, by default
``.bench_tmp/spread`` in the checkout); the summary to stdout.
This process never touches JAX, so each child has the chip to itself.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("inf")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=str(ROOT / ".bench_tmp" / "spread"))
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    log = out / f"{args.workload}.jsonl"
    sets = []
    with open(log, "a") as f:
        for k in range(args.sets):
            rows = []
            for seed in args.seeds:
                t = time.monotonic()
                p = subprocess.run(
                    [sys.executable, str(HERE / "run.py"), "--workload",
                     args.workload, "--seed", str(seed), "--seconds",
                     str(args.seconds), "--trace", str(args.trace)],
                    cwd=ROOT, capture_output=True, text=True)
                wall = time.monotonic() - t
                lines = p.stdout.strip().splitlines()
                line = json.loads(lines[-1]) if p.returncode == 0 and \
                    lines else None
                rec = {"set": k, "seed": seed, "rc": p.returncode,
                       "wall_s": wall, "result": line}
                if line is None:
                    rec["stderr_tail"] = p.stderr[-3000:]
                f.write(json.dumps(rec) + "\n")
                f.flush()
                checks = line and {c: v["value"] for c, v in
                                   line["checks"].items()}
                print(f"set {k} seed {seed} rc {p.returncode} wall "
                      f"{wall:.1f} s correct "
                      f"{line and line['correct']} "
                      f"{line and {m: v['value'] for m, v in line['metrics'].items()}} "
                      f"checks {checks}", flush=True)
                rows.append(line)
            sets.append(rows)
    for k, rows in enumerate(sets):
        good = [r for r in rows if r]
        if len(good) < 3:
            continue
        for m in good[0]["metrics"]:
            vals = [r["metrics"][m]["value"] for r in good]
            print(f"set {k} {m}: median {statistics.median(vals)!r} "
                  f"spread {spread(vals)!r} n {len(vals)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
