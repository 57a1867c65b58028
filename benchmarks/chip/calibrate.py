#!/usr/bin/env python3
"""Readings that a cell's ``correct`` limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload yi24-decode \
        --seeds 12 [--first-seed N]

In one process (set-up is long, weights are cheap): for each seed, put
that seed's weights in the cell's engine, serve the first batches of that
seed's schedule at the cell's own batch size and lengths, just enough to
hold the mix's longest request and as many requests as a run compares,
sample the rows a run would, and read

- ``program``: the gaps of the served tokens below the float32
  reference's best (the widest is what a run compares), and
- ``int8``, ``fp8``: the same for each control's own first choices,

each as the widest gap, the mean gap and the share of positions off the
reference's first choice, and whether ``run_cell.py`` would call each of
the three correct under the cell's limits (``harness.judge``, the same
decision a run makes). A limit goes between the largest ``program``
reading (the lower) and the smallest control reading (the upper; PERF.md
gives both). One JSON line per seed, then a summary line per number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import jax  # noqa: E402

import harness as H  # noqa: E402
import reference  # noqa: E402
import run_cell  # noqa: E402
import system  # noqa: E402
import traffic  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

CONTROLS = ("int8", "fp8")


def main(argv=None, *, root: Path = H.CHECKOUT,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=4_000_000_001)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload, root)

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        print(f"calibrate: needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    used = devices[:cell.chips]
    cfg = cell.config
    n_rows = cell.traffic["check_rows"]
    engine = None
    readings = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        batches = traffic.schedule(cell.traffic, cfg["vocab_size"], seed)
        longest = max(len(p) + m for b in batches for p, m in b)
        need = next(i for i in range(1, len(batches) + 1)
                    if sum(len(b) for b in batches[:i]) >= n_rows
                    and any(len(p) + m == longest
                            for b in batches[:i] for p, m in b))
        if engine is None:
            engine = system.build_engine(cell.arch, cfg, used, seed)
            run_cell.warm_up(engine, batches)
        else:
            system.set_weights(engine, cell.arch, cfg, seed)
        window = [run_cell.serve_window(engine, [b], 0.0)[0][0]
                  for b in batches[:need]]
        engine.params_home = None        # the reference needs the memory
        rows = H.check_sample(window, n_rows, seed)
        g = reference.gaps(cell.arch.logits, cfg, seed, rows, CONTROLS,
                           device=used[0])
        rec = {"seed": seed, "rows": len(rows), "batches": need, **g,
               "correct": {k: H.judge(g[k], 0, cell.limits)[0]
                           for k in ("program", *CONTROLS)},
               "seconds": time.perf_counter() - t0}
        readings.append(rec)
        print(json.dumps(rec), flush=True)
    for stat in ("max", "mean", "off"):
        lower = max(r["program"][stat] for r in readings)
        summary = {"summary": args.workload,
                   "seeds": len(readings), "number": stat, "lower": lower}
        for mode in CONTROLS:
            upper = min(r[mode][stat] for r in readings)
            summary[mode] = {"upper": upper,
                             "ratio": upper / lower if lower else None}
        print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
