"""The system under test.

Builds ``repro.launch.serve.ServeEngine`` for a configuration file, on a
``(data, model)`` mesh, and puts the benchmark's own weights into it in the
program's parameter tree, which the configuration's architecture
(``architectures/``) maps. Nothing else in the benchmark imports the
program at module level, and the reference imports nothing of it.
"""

from __future__ import annotations

import gc
import sys
from pathlib import Path

import jax

import weights as W

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from repro.config.base import ParallelConfig  # noqa: E402
from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, make_mesh  # noqa: E402
from repro.launch.serve import Request, ServeEngine  # noqa: E402


def build_engine(arch, config: dict, devices, seed: int):
    """``ServeEngine`` for ``config``, of the architecture ``arch``, on
    ``devices`` with ``seed``'s weights."""
    mesh = make_mesh((config["mesh"]["data"], config["mesh"]["model"]),
                     (DATA_AXIS, MODEL_AXIS), devices)
    parallel = ParallelConfig(fsdp=False,
                              attention_kernel=config["attention_kernel"])
    engine = ServeEngine(arch.program_config(config), mesh=mesh,
                         parallel=parallel)
    set_weights(engine, arch, config, seed)
    return engine


def set_weights(engine, arch, config: dict, seed: int) -> None:
    """Put ``seed``'s weights in place of the engine's (the engine draws
    weights of its own when it is built)."""
    engine.params_home = None
    gc.collect()
    engine.params_home = arch.program_params(
        engine.model, arch.sizes(config), W.seed_key(seed))
    jax.block_until_ready(engine.params_home)
