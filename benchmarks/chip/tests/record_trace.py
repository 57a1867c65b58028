"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

    python benchmarks/chip/tests/record_trace.py OUT_DIR [--model N]

Serves one tiny batch (2 layers, head size 128) through ``ServeEngine`` on a
mesh of ``(data=1, model=N)`` under the JAX profiler, with the harness's
``bench.prefill`` / ``bench.decode`` annotations around the two calls, and
copies the ``.xplane.pb`` to ``OUT_DIR/trace_model<N>.xplane.pb``. It also
prints each plane's lines with their event counts and a few event names,
which is how the reduction's plane and line names were chosen.
"""

from __future__ import annotations

import argparse
import dataclasses
import shutil
import sys
import tempfile
from collections import Counter
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(CHECKOUT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("out_dir")
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.config.base import ParallelConfig, get_config
    from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
    from repro.launch.serve import Request, ServeEngine

    cfg = dataclasses.replace(
        get_config("yi-9b"), num_layers=2, d_model=512, num_heads=8,
        num_kv_heads=4, head_dim=128, d_ff=1024, vocab_size=2048)
    mesh = make_mesh((1, args.model), (DATA_AXIS, MODEL_AXIS))
    engine = ServeEngine(cfg, mesh=mesh, parallel=ParallelConfig(
        fsdp=False, attention_kernel="pallas"))
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 2048, 256).astype(np.int32), 4)
            for i in range(4)]
    engine.serve(reqs)                      # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.prefill"):
            h = engine.prefill(reqs)
        with jax.profiler.TraceAnnotation("bench.decode"):
            engine.decode(h)
    jax.profiler.stop_trace()
    src = next(Path(tmp).rglob("*.xplane.pb"))
    out = Path(args.out_dir)
    out.mkdir(parents=True, exist_ok=True)
    dst = out / f"trace_model{args.model}.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(f"wrote {dst} ({dst.stat().st_size} bytes)")

    pd = jax.profiler.ProfileData.from_file(str(dst))
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = Counter(e.name for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events; "
                  f"top {names.most_common(8)}")
            if evs:
                print(f"    first start_ns {evs[0].start_ns} "
                      f"dur {evs[0].duration_ns}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
