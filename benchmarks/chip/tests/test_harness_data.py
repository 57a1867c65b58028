"""The harness is driven by data: a cell added only as files and entries
is found and scheduled; the committed benchmark is complete."""

import json

import numpy as np
import pytest

import harness as H
import traffic
import weights
from conftest import LLAMA, TINY_CONFIG, TINY_TRAFFIC


def test_tiny_cell_added_as_files_is_found(tiny_root):
    cell = H.load_cell("tiny-cell", tiny_root)
    assert cell.config == TINY_CONFIG
    assert cell.arch.__file__ == str(
        tiny_root / "bench" / "architectures" / "LlamaForCausalLM.py")
    assert cell.traffic == TINY_TRAFFIC
    assert cell.limits["max_gap"]["limit"] > 0
    assert [m["name"] for m in cell.end_to_end] == [
        "gen_tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"]


def test_tiny_cell_is_scheduled(tiny_root):
    cell = H.load_cell("tiny-cell", tiny_root)
    a = traffic.schedule(cell.traffic, 512, 2 ** 40 + 1)
    b = traffic.schedule(cell.traffic, 512, 2 ** 40 + 1)
    c = traffic.schedule(cell.traffic, 512, 2 ** 40 + 2)
    assert len(a) == TINY_TRAFFIC["batches"]
    assert all(len(x) == TINY_TRAFFIC["batch_size"] for x in a)
    assert all(np.array_equal(p, q) and m == n
               for x, y in zip(a, b) for (p, m), (q, n) in zip(x, y))
    # another seed: other tokens, the same shapes in the same order
    assert any(not np.array_equal(p, q)
               for x, y in zip(a, c) for (p, _), (q, _) in zip(x, y))
    assert list(traffic.shapes(a)) == list(traffic.shapes(c)) == [
        (32, 16), (64, 16)]
    assert sorted(m for x in a for _, m in x) == [8] * 4 + [16] * 4


def test_metric_reader_and_scope_found_by_name(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [
        {"name": "ops.x", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels", "moves": "gen_tok_s"},
        {"name": "elsewhere", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels", "moves": "gen_tok_s",
         "workloads": ["another-cell"]}]
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    (tiny_root / "bench" / "metrics" / "ops.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    cell = H.load_cell("tiny-cell", tiny_root)
    assert [m["name"] for m in cell.per_layer] == ["ops.x"]
    assert H.reader("ops.x", tiny_root / "bench").read(None) == 42.0
    with pytest.raises(H.BenchError):
        H.reader("elsewhere", tiny_root / "bench")


def test_missing_files_are_refused(tiny_root):
    (tiny_root / "bench" / "limits" / "tiny-cell.json").unlink()
    with pytest.raises(H.BenchError, match="does not exist"):
        H.load_cell("tiny-cell", tiny_root)


def test_committed_benchmark_is_complete():
    bench = json.loads((H.CHECKOUT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = H.load_cell(w["name"])
        assert set(cell.limits) == set(H.GAP_NUMBERS)
        for lim in cell.limits.values():
            assert lim["lower"] < lim["limit"] < lim["upper"]
        assert cell.config["mesh"]["data"] * cell.config["mesh"]["model"] \
            == cell.chips
        for m in cell.per_layer:
            assert callable(H.reader(m["name"]).read)
    for c in bench["configs"]:
        assert (H.CHECKOUT / c["file"]).is_file()


@pytest.mark.parametrize("stats,failed,want", [
    ({"max": 0.1, "mean": 0.001}, 0, True),
    ({"max": 0.3, "mean": 0.001}, 0, False),
    ({"max": 0.1, "mean": 0.009}, 0, False),
    ({"max": 0.1, "mean": 0.001}, 1, False)],
    ids=["within", "max_over", "mean_over", "malformed"])
def test_judge_holds_every_number_to_its_limit(stats, failed, want):
    limits = {"max_gap": {"limit": 0.25}, "mean_gap": {"limit": 0.004}}
    correct, checks = H.judge(stats, failed, limits)
    assert correct is want
    assert checks == {"max_gap": {"value": stats["max"], "limit": 0.25},
                      "mean_gap": {"value": stats["mean"], "limit": 0.004},
                      "malformed": {"value": failed, "limit": 0}}


@pytest.mark.parametrize("n,w,want", [
    (32, [0.5, 0.3, 0.2], [16, 10, 6]), (2, [0.5, 0.5], [1, 1]),
    (10, [0.4, 0.3, 0.3], [4, 3, 3]), (3, [1, 1], [2, 1])])
def test_apportion(n, w, want):
    assert traffic.apportion(n, w) == want


def test_round_robin_prefixes_keep_proportion():
    seq = traffic.round_robin([0.5, 0.3, 0.2], 100)
    for k in range(1, 101):
        counts = np.bincount(seq[:k], minlength=3)
        assert np.all(np.abs(counts - k * np.array([0.5, 0.3, 0.2])) < 1.5)


def test_large_seeds_give_distinct_keys():
    import jax
    keys = [weights.seed_key(s) for s in (0, 1, 2 ** 32, 2 ** 33 + 5)]
    data = {tuple(np.asarray(jax.random.key_data(k)).tolist()) for k in keys}
    assert len(data) == 4


def test_stacked_weights_equal_per_layer_draws():
    import jax.numpy as jnp
    s = LLAMA.sizes(dict(TINY_CONFIG, vocab_size=64))
    key = weights.seed_key(2 ** 40 + 9)
    st = LLAMA.stacked(key, s)
    for layer in range(s["layers"]):
        one = LLAMA.layer_weights(key, s, layer)
        for n, a in one.items():
            assert a.dtype == jnp.bfloat16
            assert bool(jnp.array_equal(st[n][layer], a)), (n, layer)
