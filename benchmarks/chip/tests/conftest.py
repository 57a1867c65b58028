"""Shared fixtures: a tiny cell, added only as files, in a scratch root."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness as H  # noqa: E402

TINY_CONFIG = {
    "name": "tiny", "source": "test", "architectures": ["LlamaForCausalLM"],
    "program_arch": "yi-9b",
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 128, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "attention_kernel": "pallas", "mesh": {"data": 1, "model": 1}}
TINY_TRAFFIC = {
    "name": "tiny-mix", "batch_size": 2,
    "prompt_len": {"values": [32, 64], "weights": [0.5, 0.5]},
    "max_new": {"values": [8, 16], "weights": [0.5, 0.5]},
    "batches": 4, "check_rows": 3}
# set from the tiny cell's own readings (test_control.py prints them)
TINY_LIMIT = 0.02
LLAMA = H.architecture("LlamaForCausalLM")


def write_cell(root: Path, config=TINY_CONFIG, traffic=TINY_TRAFFIC,
               limit=TINY_LIMIT, chips=1) -> str:
    """A cell ``tiny`` added as files plus entries in BENCHMARK.json, in
    a root that holds the benchmark's architecture files."""
    d = root / "bench"
    shutil.copytree(HERE / "architectures", d / "architectures",
                    ignore=shutil.ignore_patterns("__pycache__"),
                    dirs_exist_ok=True)
    for sub in ("configs", "traffic", "limits", "metrics"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    (d / "configs" / "tiny.json").write_text(json.dumps(config))
    (d / "traffic" / f"{traffic['name']}.json").write_text(
        json.dumps(traffic))
    (d / "limits" / "tiny-cell.json").write_text(
        json.dumps({"max_gap": {"limit": limit}}))
    bench = {"command": ["python3", "bench/run_cell.py"], "paths": ["bench"],
             "run_seconds": 1,
             "configs": [{"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": [{"name": "tiny-cell", "config": "tiny",
                            "traffic": traffic["name"], "chips": chips,
                            "why": "test"}],
             "end_to_end": [{"name": n, "unit": "x", "better": "lower",
                             "bound": 0.1, "source": "host_clock"}
                            for n in ("gen_tok_s", "ttft_p95_ms",
                                      "tpot_p95_ms", "setup_s")],
             "per_layer": []}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny-cell"


@pytest.fixture
def tiny_root(tmp_path):
    write_cell(tmp_path)
    return tmp_path
