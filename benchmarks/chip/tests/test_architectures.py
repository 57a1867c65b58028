"""An architecture is files only: a configuration names its file by
``architectures[0]``, and a second architecture (a toy Qwen2: llama's
block with a bias on q, k and v, served by the program's registered
``qwen2-72b`` at tiny widths) runs as a cell with nothing changed outside
the cell's own root. The reference decides: left without its biases, the
same run is not correct. A configuration whose architecture has no file is
refused."""

from __future__ import annotations

import json

import pytest

import harness as H
import run_cell
from conftest import TINY_CONFIG, write_cell

SEED = str(2 ** 40 + 13)

QWEN2 = '''"""Qwen2ForCausalLM at toy scale: llama's block with q, k and v biases."""

from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import harness as H
import reference as R
import weights as W

L = H.architecture("LlamaForCausalLM", Path(__file__).resolve().parents[1])
BIAS_IDS = {"b_q": 19, "b_k": 20, "b_v": 21}
REFERENCE_BIAS = True

sizes = L.sizes
prefill_flops, decode_flops = L.prefill_flops, L.decode_flops
decode_step_bytes = L.decode_step_bytes
flash_flops, flash_bytes = L.flash_flops, L.flash_bytes


def params(s):
    return L.params(s) + s["layers"] * (s["h"] + 2 * s["kv"]) * s["dh"]


def biases(key, s, layer):
    heads = {"b_q": s["h"], "b_k": s["kv"], "b_v": s["kv"]}
    return {n: W.draw(key, i, (heads[n], s["dh"]), 1, layer)
            for n, i in BIAS_IDS.items()}


def program_config(config):
    import dataclasses
    from repro.config.base import get_config
    cfg = dataclasses.replace(
        get_config(config["program_arch"]),
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=config["torch_dtype"])
    if not cfg.qkv_bias:
        raise ValueError(f"{config['program_arch']} has no QKV bias")
    return cfg


def program_params(model, s, key):
    def make(key):
        tree = L.program_tree(key, s)
        tree["decoder"]["attn"].update(jax.vmap(
            lambda l: biases(key, s, l))(
                jnp.arange(s["layers"], dtype=jnp.uint32)))
        return tree
    return W.on_devices(model, make, key)


@partial(jax.jit, static_argnames=("mode", "theta", "eps"))
def layer(w, x, *, mode, theta, eps):
    T, d = x.shape
    H, dh = w["w_q"].shape[1:]
    KV = w["w_k"].shape[1]
    h = R.rmsnorm(x, w["ln1"], eps)
    q = R.matmul(h, w["w_q"].reshape(d, H * dh), mode).reshape(T, H, dh)
    k = R.matmul(h, w["w_k"].reshape(d, KV * dh), mode).reshape(T, KV, dh)
    v = R.matmul(h, w["w_v"].reshape(d, KV * dh), mode).reshape(T, KV, dh)
    if REFERENCE_BIAS:
        q, k, v = q + w["b_q"], k + w["b_k"], v + w["b_v"]
    a = L.attention(L.rope(q, theta), L.rope(k, theta), v)
    x = x + R.matmul(a.reshape(T, H * dh), w["w_o"].reshape(H * dh, d), mode)
    h = R.rmsnorm(x, w["ln2"], eps)
    u = jax.nn.silu(R.matmul(h, w["w_gate"], mode)) * R.matmul(
        h, w["w_up"], mode)
    return x + R.matmul(u, w["w_down"], mode)


def logits(config, seed, seqs, positions, mode="f32", device=None):
    s = sizes(config)
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    with jax.default_device(device or jax.devices()[0]):
        key = W.seed_key(seed)
        g = R.f32(L.global_weights(key, s))
        xs = []
        for t in seqs:
            ids = np.zeros(L.padded_len(len(t)), np.int32)
            ids[:len(t)] = t
            xs.append(R.embed(g["tok"], jnp.asarray(ids)))
        for i in range(s["layers"]):
            w = R.f32({**L.layer_weights(key, s, i), **biases(key, s, i)})
            xs = [layer(w, x, mode=mode, theta=theta, eps=eps) for x in xs]
        return [L.head(g, x[jnp.asarray(p)], mode=mode, eps=eps)
                for x, p in zip(xs, positions)]
'''
QWEN2_CONFIG = dict(TINY_CONFIG, name="tiny-qwen2",
                    architectures=["Qwen2ForCausalLM"],
                    program_arch="qwen2-72b", rope_theta=1000000.0)


@pytest.fixture(autouse=True)
def no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def files_outside(root):
    """(size, mtime) of every file of the benchmark and BENCHMARK.json."""
    paths = [p for p in H.HERE.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts]
    paths.append(H.CHECKOUT / "BENCHMARK.json")
    return {p: (p.stat().st_size, p.stat().st_mtime_ns) for p in paths
            if root not in p.parents}


def qwen2_cell(root, bias: bool) -> None:
    write_cell(root, config=QWEN2_CONFIG)
    src = QWEN2 if bias else QWEN2.replace("REFERENCE_BIAS = True",
                                           "REFERENCE_BIAS = False")
    (root / "bench" / "architectures" / "Qwen2ForCausalLM.py").write_text(src)


@pytest.mark.parametrize("bias", [True, False],
                         ids=["reference_with_bias", "bias_left_out"])
def test_second_architecture_is_files_only(bias, tmp_path, capsys):
    before = files_outside(tmp_path)
    qwen2_cell(tmp_path, bias)
    cell = H.load_cell("tiny-cell", tmp_path)
    assert cell.arch.program_config(cell.config).qkv_bias
    rc = run_cell.main(["--workload", "tiny-cell", "--seed", SEED,
                        "--seconds", "1"], root=tmp_path, require_tpu=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is bias, res["checks"]
    assert files_outside(tmp_path) == before


@pytest.mark.parametrize("architectures", [["NoSuchForCausalLM"], None],
                         ids=["no_file", "none_named"])
def test_missing_architecture_is_refused(architectures, tmp_path, capsys):
    config = dict(TINY_CONFIG)
    if architectures:
        config["architectures"] = architectures
    else:
        del config["architectures"]
    write_cell(tmp_path, config=config)
    with pytest.raises(H.BenchError) as err:
        H.load_cell("tiny-cell", tmp_path)
    if architectures:
        assert str(tmp_path / "bench" / "architectures" /
                   "NoSuchForCausalLM.py") in str(err.value)
    rc = run_cell.main(["--workload", "tiny-cell", "--seed", SEED,
                        "--seconds", "1"], root=tmp_path, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "architecture" in out.err
