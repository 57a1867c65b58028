#!/usr/bin/env python3
"""Two sets of runs of one cell, and the spread each metric shows.

    python3 benchmarks/chip/spread.py --workload yi24-decode \
        --seeds 11 12 13 14 15 16 [--sets 2] [--seconds 40] [--out F]

Runs ``run_cell.py`` once per seed per set, each run its own process (this
process never touches JAX, so each child gets the chips), with the same
seeds in every set. For each end-to-end metric and set it prints the
median and the spread: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median. A bound
is set from the widest spread of the sets. Each run's result line is
appended to ``--out``.
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


def spread(xs: list) -> float:
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args()
    seconds = args.seconds or json.loads(
        (HERE.parents[1] / "BENCHMARK.json").read_text())["run_seconds"]
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in args.seeds:
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, str(HERE / "run_cell.py"), "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", "0"],
                capture_output=True, text=True)
            wall = time.perf_counter() - t0
            lines = p.stdout.strip().splitlines()
            if p.returncode != 0 or not lines:
                print(f"set {k} seed {seed}: rc {p.returncode}\n"
                      f"{p.stderr[-2000:]}", flush=True)
                continue
            res = json.loads(lines[-1])
            res.update(set=k, seed=seed, wall_s=wall)
            runs.append(res)
            print(json.dumps({"set": k, "seed": seed, "wall_s": wall,
                              "correct": res["correct"],
                              "checks": res["checks"],
                              **{m: v["value"] for m, v in
                                 res["metrics"].items()}}), flush=True)
            if args.out:
                with args.out.open("a") as f:
                    f.write(json.dumps(res) + "\n")
        sets.append(runs)
    for name in sets[0][0]["metrics"] if sets and sets[0] else []:
        row = {"metric": name}
        for k, runs in enumerate(sets):
            xs = [r["metrics"][name]["value"] for r in runs]
            if len(xs) >= 2:
                row[f"median_{k}"] = statistics.median(xs)
                row[f"spread_{k}"] = spread(xs)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
