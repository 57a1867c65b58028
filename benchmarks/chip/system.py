"""The system under test, and the one place that knows its layout.

Builds ``repro.launch.serve.ServeEngine`` for a configuration file, on a
``(data, model)`` mesh, and puts the benchmark's own weights (``weights``)
into it in the program's parameter tree. Nothing else in the benchmark
imports the program, and the reference imports nothing of it.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
from pathlib import Path

import jax

import weights as W

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from repro.config.base import ParallelConfig, get_config  # noqa: E402
from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, make_mesh  # noqa: E402
from repro.launch.serve import Request, ServeEngine  # noqa: E402


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: its
    registered architecture with every size the file states."""
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
    if cfg.family != "dense" or cfg.attn_type != "full" or cfg.qkv_bias:
        raise ValueError(f"{config['name']}: the program's "
                         f"{config['program_arch']} is not a dense llama "
                         f"block, which is all the reference computes")
    return cfg


def program_params(model, s: dict, key) -> dict:
    """The benchmark's weights in the program's tree and shardings, made
    on the devices in one jitted call."""
    shardings = jax.tree.map(lambda a: a.sharding,
                             model.abstract_params(dtype=W.SERVED_DTYPE))

    def make(key):
        w = W.stacked(key, s)
        return {"embed": {"tok": w["tok"], "out": w["out"]},
                "final_norm": w["final_norm"],
                "decoder": {"ln1": w["ln1"], "ln2": w["ln2"],
                            "attn": {n: w[n] for n in
                                     ("w_q", "w_k", "w_v", "w_o")},
                            "mlp": {n: w[n] for n in
                                    ("w_gate", "w_up", "w_down")}}}
    want = jax.tree.structure(shardings)
    got = jax.tree.structure(jax.eval_shape(make, key))
    if want != got:
        raise ValueError(f"the program's parameter tree changed: {want} "
                         f"is not the benchmark's {got}")
    return jax.jit(make, out_shardings=shardings)(key)


def build_engine(config: dict, devices, seed: int):
    """``ServeEngine`` for ``config`` on ``devices`` with ``seed``'s
    weights."""
    mesh = make_mesh((config["mesh"]["data"], config["mesh"]["model"]),
                     (DATA_AXIS, MODEL_AXIS), devices)
    parallel = ParallelConfig(fsdp=False,
                              attention_kernel=config["attention_kernel"])
    engine = ServeEngine(program_config(config), mesh=mesh,
                         parallel=parallel)
    set_weights(engine, config, seed)
    return engine


def set_weights(engine, config: dict, seed: int) -> None:
    """Put ``seed``'s weights in place of the engine's (the engine draws
    weights of its own when it is built)."""
    engine.params_home = None
    gc.collect()
    engine.params_home = program_params(engine.model, W.sizes(config),
                                        W.seed_key(seed))
    jax.block_until_ready(engine.params_home)
