"""The llama architecture file reads exactly what the benchmark read before
its code moved there: ``data/pinned.json`` holds readings recorded from
that code on the CPU for the inputs made here. SHA-256 of the tiny
configuration's weights (stacked, each layer drawn alone, the global
leaves), every count at yi-9b's widths, and every pinned reader's value
and description on the recorded traces, each compared exactly; and every
eighth column of its reference logits in each mode, compared to a
relative 1e-6, since another XLA build may round a CPU matmul otherwise."""

import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

import harness as H
import trace_reduce as TR
import weights
from conftest import LLAMA, TINY_CONFIG

DATA = Path(__file__).resolve().parent / "data"
PINNED = json.loads((DATA / "pinned.json").read_text())


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_weights_bit_for_bit():
    s = LLAMA.sizes(TINY_CONFIG)
    key = weights.seed_key(2 ** 40 + 9)
    st = LLAMA.stacked(key, s)
    want = PINNED["weights"]
    assert digest(st[n] for n in sorted(st)) == want["stacked"]
    assert [digest(v for _, v in sorted(LLAMA.layer_weights(key, s, l)
                                        .items()))
            for l in range(s["layers"])] == want["layers"]
    assert digest(v for _, v in sorted(
        LLAMA.global_weights(key, s).items())) == want["globals"]


@pytest.mark.parametrize("mode", ["f32", "int8", "fp8"])
def test_reference_logits(mode):
    """Two sequences (one right-padded across a chunk border), positions
    at both ends and in between."""
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 512, 300).astype(np.int32),
            rng.integers(0, 512, 77).astype(np.int32)]
    pos = [[0, 100, 257, 299], [5, 76]]
    lg = LLAMA.logits(TINY_CONFIG, 2 ** 33 + 1, seqs, pos, mode)
    want = PINNED["logits"][mode]["every_8th_column"]
    assert len(lg) == len(want)
    for got, w in zip(lg, want):
        np.testing.assert_allclose(np.asarray(got)[:, ::8], w, rtol=1e-6,
                                   atol=1e-6)


@pytest.mark.parametrize("config", ["yi-9b-24L", "yi-9b-tp4"])
def test_counts_at_yi_9b_widths(config):
    sz = LLAMA.sizes(H.read_json(H.HERE / "configs" / f"{config}.json"))
    ctx = [129, 300, 513, 768, 8200]
    got = {
        "params": LLAMA.params(sz),
        "layer_matrix_params": LLAMA.layer_matrix_params(sz),
        "prefill_flops": LLAMA.prefill_flops(sz, [128, 256, 512, 2048, 8192]),
        "decode_flops": LLAMA.decode_flops(sz, ctx),
        "decode_step_bytes.1": LLAMA.decode_step_bytes(sz, ctx),
        "decode_step_bytes.4": LLAMA.decode_step_bytes(sz, ctx, 4),
        "kv_bytes": LLAMA.kv_bytes(sz, 1000),
        "attention_flops": LLAMA.attention_flops(sz, 7, 700),
        "flash_flops": LLAMA.flash_flops(sz, 2, 8192),
        "flash_bytes": LLAMA.flash_bytes(sz, 32, 512),
    }
    assert got == PINNED["counts"][config]


# the model record_trace.py serves: two batches of 4 requests, prompt 256,
# 4 new tokens each
RECORDED = {"hidden_size": 512, "intermediate_size": 1024,
            "num_attention_heads": 8, "num_key_value_heads": 4,
            "head_dim": 128, "num_hidden_layers": 2, "vocab_size": 2048}


@pytest.mark.parametrize("name,chips", [("trace_model1", 1),
                                        ("trace_spans1", 1),
                                        ("trace_model4", 4)])
def test_pinned_readers_on_the_recorded_traces(name, chips):
    tr = TR.load(DATA / f"{name}.xplane.pb")
    cell = H.Cell(name="recorded", paths="bench", chips=chips,
                  config=RECORDED, arch=LLAMA, traffic={}, limits={},
                  end_to_end=[], per_layer=[])
    b = H.Batch(prompts=[np.zeros(256, np.int32)] * 4, max_new=[4] * 4,
                served=[], t_submit=0.0, t_first=0.0, t_done=1.0)
    run = H.Run(cell, LLAMA.sizes(RECORDED), H.peaks("TPU v5 lite"),
                [b, b], tr)
    want = PINNED["readers"][name]
    for metric, (value, description) in want.items():
        mod = H.reader(metric)
        got = mod.read(run)
        assert got == value, metric
        if description is not None:
            assert mod.describe(run) == description, metric
