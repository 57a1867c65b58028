"""The readers of the engine's own spans (``program_spans.py`` and the four
metrics on it): exact values on a synthetic trace, nothing on the recorded
traces of a program without the spans, and plausible values on a small
trace recorded on a TPU v5e with ``record_trace.py`` from a program that
has them (``trace_spans1.xplane.pb``)."""

from pathlib import Path

import numpy as np
import pytest

import harness as H
import program_spans as PS
import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data"
READERS = ("decode_step_ms", "decode_step_idle_ms", "prefill_idle_ms",
           "decode_useful_share")


def batch(prompt_len=8, max_new=(2, 1), t_first=0.0, t_done=2e-7):
    return H.Batch(prompts=[np.zeros(prompt_len, np.int32)] * len(max_new),
                   max_new=list(max_new), served=[], t_submit=0.0,
                   t_first=t_first, t_done=t_done)


def synthetic(busy_chip1=False):
    """One prefill call (0-100) and one decode call of two steps (100-300),
    each phase timed so that the device's idle time in it is known: step 1
    idles 10 ns in inputs, 5 in dispatch, 10 in readback and 10 in emit;
    step 2 10 in inputs, 2 in dispatch, 15 in readback, 5 in a collection
    inside readback, 1 between readback and emit, 9 in emit; the prefill
    20 in inputs, 5 in dispatch, 10 in readback."""
    host = [("serve.prefill", 0, 100), ("serve.inputs", 0, 20),
            ("serve.dispatch", 20, 30), ("serve.sample", 30, 35),
            ("serve.readback", 35, 100),
            ("serve.decode", 100, 300),
            ("serve.decode_step", 100, 200), ("serve.inputs", 100, 110),
            ("serve.dispatch", 110, 120), ("serve.sample", 120, 124),
            ("serve.readback", 125, 190), ("serve.emit", 190, 200),
            ("serve.decode_step", 200, 300), ("serve.inputs", 200, 210),
            ("serve.dispatch", 210, 220), ("serve.sample", 220, 225),
            ("serve.readback", 225, 290), ("python.gc", 280, 285),
            ("serve.emit", 291, 300), ("Execute", 111, 112)]
    ops = {0: TR.nest([("fusion.1", 25, 90), ("while.2", 115, 180),
                       ("fusion.3", 120, 130), ("fusion.4", 212, 270)])}
    if busy_chip1:
        ops[1] = TR.nest([("fusion.1", 0, 300)])
    return TR.Trace(ops, host, [])


def reader(name):
    return H.reader(name)


def run_of(tr, batches):
    return H.Run(cell=None, sizes={}, peak={}, batches=batches, trace=tr)


def test_idle_split_names_each_phase():
    tr = synthetic()
    split = PS.idle_split(tr, TR.merge(PS.spans(tr, PS.STEP)))
    assert split == {"serve.inputs": 20, "serve.dispatch": 7,
                     "serve.sample": 0, "serve.readback": 25,
                     "serve.emit": 19, "python.gc": 5, "none": 1}
    split = PS.idle_split(tr, PS.spans(tr, PS.PREFILL))
    assert split["serve.inputs"] == 20 and split["serve.dispatch"] == 5
    assert split["serve.readback"] == 10 and sum(split.values()) == 35


def test_longest_idle_names_its_phase():
    tr = synthetic()
    # the steps tile the call, so idle time runs on across their border
    # (180-212); its middle falls in the first step's emit
    assert PS.longest_idle(tr, TR.merge(PS.spans(tr, PS.STEP))) == (
        32, "serve.emit")
    assert PS.longest_idle(tr, PS.spans(tr, PS.PREFILL)) == (
        25, "serve.inputs")


def test_steps_pair_with_their_call():
    tr = synthetic()
    assert PS.steps_per_call(tr) == [[(100, 200), (200, 300)]]


@pytest.mark.parametrize("busy_chip1,scale", [(False, 1.0), (True, 0.5)],
                         ids=["one_chip", "two_chips"])
def test_readers_on_a_synthetic_trace(busy_chip1, scale):
    """Exact values; a second chip busy throughout halves the idle time,
    which is averaged over the chips."""
    run = run_of(synthetic(busy_chip1), [batch()])
    got = {n: reader(n).read(run) for n in READERS}
    assert got["decode_step_ms"] == pytest.approx(100e-6)
    assert got["decode_step_idle_ms"] == pytest.approx(77e-6 / 2 * scale)
    assert got["prefill_idle_ms"] == pytest.approx(35e-6 * scale)
    assert got["decode_useful_share"] == pytest.approx(75.0)
    step = reader("decode_step_ms").describe(run)
    assert "2 steps" in step and "prompt length 8" in step
    # both steps last 100 ns; the first, with 35 ns idle, is the longest
    assert "idle 0.0000 ms, most in serve.inputs" in step
    assert "longest gap 0.0000 ms in serve.inputs" in reader(
        "prefill_idle_ms").describe(run)
    idle = reader("decode_step_idle_ms").describe(run)
    assert "serve.readback 0.0000 ms/step" in idle and "(32.5%)" in idle
    # the window's two steps (200 ns) over the call's host time (200 ns)
    assert "1.0000-1.0000" in reader("decode_useful_share").describe(run)


def test_useful_share_needs_one_batch_per_call():
    run = run_of(synthetic(), [batch(), batch()])
    assert reader("decode_useful_share").read(run) is None


@pytest.mark.parametrize("name", ["trace_model1", "trace_model4"])
def test_readers_read_nothing_without_program_spans(name):
    """The recorded traces of a program with only the harness's spans."""
    tr = TR.load(DATA / f"{name}.xplane.pb")
    run = run_of(tr, [batch(256, [4] * 4)] * 2)
    for n in READERS:
        assert reader(n).read(run) is None, n


@pytest.fixture(scope="module")
def spans1():
    path = DATA / "trace_spans1.xplane.pb"
    if not path.exists():
        pytest.fail(f"{path} is missing; record it with record_trace.py")
    return TR.load(path)


def test_recorded_trace_has_every_program_span(spans1):
    names = {n for n, _, _ in spans1.host}
    assert {PS.PREFILL, PS.DECODE, PS.STEP, *PS.PHASES} <= names
    assert len(PS.spans(spans1, PS.PREFILL)) == 2
    assert [len(c) for c in PS.steps_per_call(spans1)] == [4, 4]


def test_readers_on_the_recorded_trace(spans1):
    """Two batches of 4 requests (prompt 256, 4 new tokens each) served by
    a 2-layer model on one chip."""
    run = run_of(spans1, [batch(256, [4] * 4, 0.0, 1.0)] * 2)
    step = reader("decode_step_ms").read(run)
    idle = reader("decode_step_idle_ms").read(run)
    assert 0 < idle < step < 100
    calls = PS.spans(spans1, PS.PREFILL)
    longest = max(e - s for s, e in calls) * 1e-6
    assert 0 < reader("prefill_idle_ms").read(run) < longest
    assert reader("decode_useful_share").read(run) == 100.0
    for n in READERS:
        assert reader(n).describe(run)
