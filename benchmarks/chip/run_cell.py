#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run_cell.py --workload yi24-decode --seed 7 \
        --seconds 40 --trace 0

Needs a TPU with at least the cell's chips; without one it prints no
result and exits 1. A run builds ``ServeEngine`` for the cell's
configuration with weights drawn from ``--seed``, draws the whole request
schedule from the seed, warms up each padded shape the schedule uses, then
serves batches back to back (closed loop: prefill, then decode to the
batch's longest request) until ``--seconds`` have passed and the batch in
flight has finished. The window ends there; rates divide by it.

``--trace 0`` reports the end-to-end metrics, ``--trace 1`` runs the same
window under the profiler and reports the per-layer metrics read from its
trace. Either way, once the window has closed and the engine is freed, a
sample of the finished requests is compared with the float32 reference
(the configuration's architecture's ``logits``, scored by
``reference.py``) and decides ``correct``. The last line of standard
output is the result as one JSON object.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import harness as H  # noqa: E402
import reference  # noqa: E402
import system  # noqa: E402
import trace_reduce as TR  # noqa: E402
import traffic  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

PREFILL, DECODE = "bench.prefill", "bench.decode"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def warm_up(engine, batches: list) -> None:
    """One prefill of each padded shape, and two decode steps on its
    cache, which compiles (or loads) every program the window runs."""
    for (_, steps), b in traffic.shapes(batches).items():
        h = engine.prefill(requests(b))
        engine.decode(dataclasses.replace(h, max_new=min(2, steps)))


def requests(batch: list) -> list:
    return [system.Request(i, p, m) for i, (p, m) in enumerate(batch)]


def serve_window(engine, batches: list, seconds: float) -> tuple:
    """Closed loop over ``batches`` (cycled) until ``seconds`` pass and the
    batch in flight ends: ([harness.Batch], window seconds)."""
    out = []
    t0 = time.perf_counter()
    i = 0
    while True:
        b = batches[i % len(batches)]
        i += 1
        reqs = requests(b)
        t_sub = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFILL):
            h = engine.prefill(reqs)
        t_first = time.perf_counter()
        with jax.profiler.TraceAnnotation(DECODE):
            res = engine.decode(h)
        t_done = time.perf_counter()
        first = np.asarray(h.tok)[:, 0]
        out.append(H.Batch(
            prompts=[p for p, _ in b], max_new=[m for _, m in b],
            served=[[int(first[j])] + list(r.tokens)
                    for j, r in enumerate(res)],
            t_submit=t_sub, t_first=t_first, t_done=t_done))
        if t_done - t0 >= seconds:
            return out, t_done - t0


class CompileCounter:
    """Counts backend compilations (or cache loads) while active."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.active = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.n += 1


def traced(fn, trace_dir: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def per_layer(run: H.Run, cell: H.Cell) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = H.reader(m["name"])
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
            if hasattr(mod, "describe"):
                say(f"{m['name']}: {float(v)} {m['unit']}, "
                    f"{mod.describe(run)}")
    return out


def main(argv=None, *, root: Path = H.CHECKOUT, require_tpu: bool = True):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = H.load_cell(args.workload, root)
    except H.BenchError as e:
        say(f"run_cell: {e}")
        return 2

    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"run_cell: JAX found no devices: {e}")
        return 1
    dev = devices[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    if require_tpu:
        if dev.platform != "tpu" or len(devices) < cell.chips:
            say(f"run_cell: {cell.name} needs {cell.chips} TPU chip(s); "
                f"JAX found {len(devices)} {dev.platform} device(s)")
            return 1
        try:
            peak = H.peaks(dev.device_kind)
        except H.BenchError as e:
            say(f"run_cell: {e}")
            return 2
    else:
        peak = None
    used = devices[:cell.chips]

    say(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cfg = cell.config
    batches = traffic.schedule(cell.traffic, cfg["vocab_size"], args.seed)
    engine = system.build_engine(cell.arch, cfg, used, args.seed)
    warm_up(engine, batches)
    counter = CompileCounter()
    setup_s = time.perf_counter() - T0
    say(f"setup: {setup_s:.3f} s; padded shapes "
        f"{list(traffic.shapes(batches))}")

    counter.active = True
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    try:
        if trace_dir:
            window, window_s = traced(
                lambda: serve_window(engine, batches, args.seconds),
                trace_dir)
        else:
            window, window_s = serve_window(engine, batches, args.seconds)
        counter.active = False
        peak_bytes = max(d.memory_stats().get("peak_bytes_in_use", 0)
                         for d in used) if require_tpu else 0
        del engine
        gc.collect()

        e2e = H.end_to_end(window, window_s)
        say(f"window: {window_s:.3f} s, {len(window)} batches, "
            f"{e2e['samples']} requests, {counter.n} compilations inside")
        say(f"samples: ttft_p95_ms over {e2e['samples']}, tpot_p95_ms over "
            f"{e2e['samples']}")
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak_bytes}
        result = {}
        if trace_dir:
            tr = TR.load(next(Path(trace_dir).rglob("*.xplane.pb")))
            run = H.Run(cell, cell.arch.sizes(cfg), peak, window, tr)
            metrics = per_layer(run, cell)
            lo, hi = tr.extent()
            device["busy_s"] = TR.mean_busy_ns(tr, [(lo, hi)]) * 1e-9
            device["window_s"] = (hi - lo) * 1e-9
            result["breakdown"] = {"device_ops": TR.top_ops(tr),
                                   "idle_gaps": TR.idle_gaps(tr)}
        else:
            metrics = {m["name"]: {"value": (setup_s if m["name"] == "setup_s"
                                             else e2e[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    failed = sum(H.malformed(b, cfg["vocab_size"]) for b in window)
    rows = H.check_sample(window, cell.traffic["check_rows"], args.seed)
    t = time.perf_counter()
    g = reference.gaps(cell.arch.logits, cfg, args.seed, rows,
                       device=used[0])
    correct, checks = H.judge(g["program"], failed, cell.limits)
    say(f"reference: {len(rows)} requests, {g['tokens']} served tokens, "
        f"{time.perf_counter() - t:.3f} s")
    for k, c in checks.items():
        say(f"check: {k} = {c['value']!r} (limit {c['limit']!r})")
    result["window"] = {"seconds": window_s, "batches": len(window),
                        "compilations": counter.n,
                        "prompt_len": [b.padded_len for b in window],
                        "decode_s": [b.t_done - b.t_first for b in window]}
    print(json.dumps({
        "correct": correct, "attempted": e2e["samples"], "failed": failed,
        "metrics": metrics, "device": device, **result, "checks": checks}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
