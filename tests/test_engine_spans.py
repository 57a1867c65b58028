"""ServeEngine's spans on the profiler's clock: a tiny engine served under
``jax.profiler.trace``, read back with ``ProfileData``.

Every call and host phase is annotated whether or not an obs ``Tracer`` is
attached; an attached one gets the same events as without the annotations,
and only it feeds the straggler detector."""

import gc
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.config.base import get_config
from repro.launch.serve import Request, ServeEngine
from repro.obs.trace import NULL_TRACER, Tracer

STEPS = 3
DECODE_PHASES = ["serve.inputs", "serve.dispatch", "serve.sample",
                 "serve.readback", "serve.emit"]
PREFILL_PHASES = DECODE_PHASES[:-1]


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("yi-9b").reduced()
    eng = ServeEngine(cfg)
    eng.serve(requests(cfg))              # compile outside any trace
    return eng


def requests(cfg, n=2):
    rng = np.random.default_rng(0)
    return [Request(i, rng.integers(0, cfg.vocab_size, 16).astype(np.int32),
                    STEPS) for i in range(n)]


def host_events(trace_dir: Path) -> list:
    """[(name, start_ns, end_ns, stats)] of every host event, by start."""
    path = next(trace_dir.rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    return sorted(((e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for p in pd.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events),
                  key=lambda e: (e[1], -e[2]))


def served_under_profiler(engine, tmp_path, tracer):
    engine.tracer = tracer
    engine.straggler.times.clear()
    try:
        with jax.profiler.trace(str(tmp_path)):
            res = engine.decode(engine.prefill(requests(engine.cfg)))
            gc.collect()
    finally:
        engine.tracer = NULL_TRACER
    return res, host_events(tmp_path)


def named(events, name):
    return [e for e in events if e[0] == name]


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(params=["null_tracer", "obs_tracer"])
def traced(request, engine, tmp_path):
    tracer = NULL_TRACER if request.param == "null_tracer" else Tracer()
    res, events = served_under_profiler(engine, tmp_path, tracer)
    return res, events, tracer


def test_every_span_appears(traced):
    _, events, _ = traced
    names = {e[0] for e in events}
    assert {"serve.prefill", "serve.decode", "serve.decode_step",
            "python.gc", *DECODE_PHASES} <= names


def test_steps_tile_the_decode_call_with_their_phases_inside(traced):
    res, events, _ = traced
    (call,) = named(events, "serve.decode")
    assert call[3] == {"batch": 2, "steps": STEPS}
    steps = named(events, "serve.decode_step")
    assert [s[3]["step"] for s in steps] == list(range(STEPS))
    assert all(inside(s, call) for s in steps)
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))
    for step in steps:
        phases = [e[0] for e in events
                  if e[0] in DECODE_PHASES and inside(e, step)]
        assert phases == DECODE_PHASES
    # every decode-side phase lies in some step: none runs between steps
    for e in events:
        if e[0] in DECODE_PHASES and inside(e, call):
            assert any(inside(e, s) for s in steps)
    assert all(len(r.tokens) == STEPS for r in res)


def test_prefill_phases_inside_the_prefill_call(traced):
    _, events, _ = traced
    (call,) = named(events, "serve.prefill")
    assert call[3] == {"batch": 2, "prompt_len": 16}
    phases = [e[0] for e in events
              if e[0] in DECODE_PHASES and inside(e, call)]
    assert phases == PREFILL_PHASES


def test_obs_events_and_straggler_only_with_an_enabled_tracer(traced,
                                                              engine):
    """The obs tracer gets the events it always got, and only it feeds the
    straggler detector (whose one reader is its metrics snapshot)."""
    _, _, tracer = traced
    fed = len(engine.straggler.times)
    if not tracer.enabled:
        assert tracer.events == ()
        assert fed == 0
        return
    track = ("serving", "engine")
    want = [("i", "serve.admit", track, "serve",
             {"rid": i, "prompt_len": 16, "max_new": STEPS})
            for i in range(2)]
    want += [("B", "serve.prefill", track, "serve",
              {"batch": 2, "prompt_len": 16}),
             ("E", "serve.prefill", track, "serve", None)]
    for s in range(STEPS):
        want += [("B", "serve.decode_step", track, "serve",
                  {"step": s, "batch": 2}),
                 ("E", "serve.decode_step", track, "serve", None)]
    assert [(e.kind, e.name, e.track, e.cat, e.args)
            for e in tracer.events] == want
    assert fed == STEPS
    assert "serve.straggler.median_s" in str(tracer.metrics.to_json())
