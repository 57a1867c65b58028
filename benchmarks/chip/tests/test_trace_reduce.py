"""Trace reduction: interval arithmetic by hand, and small traces recorded
on a TPU v5e, one chip and four (``record_trace.py [--model 4]``: a 2-layer
model served under the profiler with the harness's annotations)."""

from pathlib import Path

import pytest

import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data"


def test_merge_overlap_subtract():
    a = TR.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert a == [(0, 3), (5, 9)]
    assert TR.overlap(a, [(2, 6)]) == 2
    assert TR.subtract(a, [(1, 2), (4, 6), (8, 20)]) == [(0, 1), (2, 3),
                                                       (6, 8)]
    assert TR.length(a) == 7


def test_nesting_gives_self_time_and_leaves():
    got = TR.nest([("while.1", 0, 10), ("fusion.2", 1, 3), ("copy.3", 4, 5),
                   ("fusion.4", 12, 13)])
    assert got == [("while.1", 0, 10, 7, False), ("fusion.2", 1, 3, 2, True),
                   ("copy.3", 4, 5, 1, True), ("fusion.4", 12, 13, 1, True)]
    assert TR.short("%fusion.12 = bf16[2,4]{1,0} fusion(%p), kind=kLoop") \
        == "fusion.12"


def synthetic():
    ops = {0: TR.nest([("fusion.1", 0, 10), ("all-reduce.2", 10, 20),
                       ("while.9", 30, 40), ("all-reduce-done", 30, 34),
                       ("fusion.3", 35, 38), ("flash_attention.4", 50, 60)]),
           1: TR.nest([("fusion.1", 0, 20)])}
    spans = [("bench.prefill", 0, 24), ("bench.decode", 24, 75)]
    return TR.Trace(ops, spans + [("Execute", 36, 37), ("wait", 60, 70)],
                    spans)


def test_busy_exposed_and_gaps_on_a_synthetic_trace():
    tr = synthetic()
    dec = tr.windows("bench.decode")
    assert TR.busy_ns(tr, 0, dec) == 20
    assert TR.busy_ns(tr, 1, tr.windows("bench.prefill")) == 20
    assert TR.busy_ns(tr, 1, dec) == 0
    assert TR.mean_busy_ns(tr, dec) == 10
    # all-reduce-done 30-34 runs inside the while 30-40, which is no leaf
    assert TR.exposed_collective_ns(tr, 0, dec) == 4
    assert TR.exposed_collective_ns(tr, 0, tr.windows("bench.prefill")) == 10
    assert TR.op_ns(tr, 0, lambda n: "flash" in n, [tr.extent()]) == 10
    (name, sec), = TR.top_ops(tr, 1)
    assert name == "fusion.1" and sec == pytest.approx(15e-9)
    gaps = TR.idle_gaps(tr, 2)
    assert [g[0] for g in gaps] == ["bench.decode > wait", "bench.decode"]
    assert [g[1] for g in gaps] == pytest.approx([15e-9, 10e-9])


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "trace_model1.xplane.pb"
    if not path.exists():
        pytest.fail(f"{path} is missing; record it with record_trace.py")
    return TR.load(path)


def test_recorded_trace_has_one_chip_and_the_annotations(recorded):
    assert recorded.chips == [0]
    names = {n for n, _, _ in recorded.spans}
    assert names == {"bench.prefill", "bench.decode"}
    assert len(recorded.windows("bench.prefill")) == 2
    assert len(recorded.windows("bench.decode")) == 2


def test_recorded_trace_busy_fits_its_windows(recorded):
    for name in ("bench.prefill", "bench.decode"):
        win = recorded.windows(name)
        busy = TR.busy_ns(recorded, 0, win)
        assert 0 < busy <= TR.length(win)
    lo, hi = recorded.extent()
    assert TR.busy_ns(recorded, 0, [(lo, hi)]) <= hi - lo
    assert TR.exposed_collective_ns(recorded, 0, [(lo, hi)]) == 0


def test_recorded_trace_names_the_flash_kernel(recorded):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "flash", DATA.parents[1] / "metrics" / "flash_attention_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    lo, hi = recorded.extent()
    # one kernel a layer: 2 layers, 2 prefills. (Host and device clocks
    # agree to about a millisecond, so a millisecond-long span of this
    # tiny trace does not hold its own kernels.)
    kernels = [o for o in recorded.ops[0] if mod.KERNEL.search(o[0])]
    assert len(kernels) == 4 and all(o[4] for o in kernels)
    assert TR.op_ns(recorded, 0, mod.KERNEL.search, [(lo, hi)]) == sum(
        o[2] - o[1] for o in kernels)


def test_recorded_trace_breakdown(recorded):
    ops = TR.top_ops(recorded)
    assert 0 < len(ops) <= 10
    assert all(v > 0 for _, v in ops)
    gaps = TR.idle_gaps(recorded)
    assert 0 < len(gaps) <= 10
    assert all(v > 0 and label for label, v in gaps)


def test_recorded_four_chip_trace_has_exposed_collectives():
    """The same tiny model on a (data=1, model=4) mesh: four chip planes,
    and collectives (all-reduce, all-gather, ...) that the reduction finds
    and that leave the chips waiting for part of their busy time."""
    tr = TR.load(DATA / "trace_model4.xplane.pb")
    assert tr.chips == [0, 1, 2, 3]
    for c in tr.chips:
        names = {o[0] for o in tr.ops[c] if TR.COLLECTIVE.search(o[0])}
        assert any(n.startswith("all-reduce") for n in names)
        win = [tr.extent()]
        assert 0 < TR.exposed_collective_ns(tr, c, win) < TR.busy_ns(tr, c,
                                                                      win)
