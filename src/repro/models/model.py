"""Model facade: ties configs, specs, sharding, and step functions together."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.models import params as pm
from repro.models.context import MCtx
from repro.models.decode import cache_specs, decode_step, prefill
from repro.models.sharding import logical_rules, named_sharding
from repro.models.transformer import loss_fn, model_specs


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    mctx: MCtx

    @classmethod
    def create(cls, cfg: ModelConfig, mesh,
               parallel: ParallelConfig = ParallelConfig(),
               seq_sharded_cache: bool = False) -> "Model":
        return cls(cfg, MCtx(mesh, parallel,
                             seq_sharded_cache=seq_sharded_cache))

    # -- specs ------------------------------------------------------------
    @property
    def specs(self) -> dict:
        return model_specs(self.cfg, self.mctx.mesh)

    def param_sharding(self, spec: pm.ParamSpec, memory_kind=None):
        return named_sharding(self.mctx.mesh, self.mctx.rules, spec.axes,
                              spec.shape, memory_kind=memory_kind)

    def abstract_params(self, memory_kinds: Optional[dict] = None,
                        dtype=None):
        """ShapeDtypeStruct tree with NamedShardings (dry-run inputs).

        memory_kinds: optional {path_prefix: kind} — e.g. from the placement
        engine — applied by top-level param group name. dtype: override
        (e.g. jnp.bfloat16 for serve-mode weights).
        """
        def mk(path, s: pm.ParamSpec):
            kind = None
            if memory_kinds:
                kind = memory_kinds.get(path[0], None)
            if kind == "device":
                kind = None
            return jax.ShapeDtypeStruct(
                s.shape, jnp.dtype(dtype or s.dtype),
                sharding=self.param_sharding(s, kind))
        return _tree_map_with_path(mk, self.specs)

    def abstract_cache(self, B: int, S: int):
        cspecs = cache_specs(self.cfg, self.mctx, B, S)
        def mk(path, s: pm.ParamSpec):
            return jax.ShapeDtypeStruct(
                s.shape, jnp.dtype(s.dtype), sharding=self.param_sharding(s))
        return _tree_map_with_path(mk, cspecs)

    # -- init ---------------------------------------------------------------
    def init(self, rng, dtype=None) -> dict:
        """Parameters from ``rng``, made inside one jit: each leaf is drawn
        directly in ``dtype`` (default: its spec's) with its own sharding,
        so every shard is created on its own device and no float32 copy of
        a bf16 model exists anywhere."""
        specs = self.specs
        if dtype is not None:
            specs = jax.tree.map(
                lambda s: dataclasses.replace(s, dtype=jnp.dtype(dtype).name),
                specs, is_leaf=lambda x: isinstance(x, pm.ParamSpec))
        shardings = jax.tree.map(lambda a: a.sharding,
                                 self.abstract_params(dtype=dtype))
        return jax.jit(lambda key: pm.init_params(specs, key),
                       out_shardings=shardings)(rng)

    def init_cache(self, B: int, S: int) -> dict:
        cspecs = cache_specs(self.cfg, self.mctx, B, S)
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)), cspecs,
            is_leaf=lambda x: isinstance(x, pm.ParamSpec))

    # -- steps ----------------------------------------------------------------
    def loss(self, params, batch):
        return loss_fn(params, self.cfg, self.mctx, batch)

    def prefill(self, params, batch, max_len: int = 0):
        return prefill(params, self.cfg, self.mctx, batch, max_len=max_len)

    def decode(self, params, cache, tokens, pos):
        return decode_step(params, self.cfg, self.mctx, cache, tokens, pos)

    @property
    def num_params(self) -> int:
        return pm.count_params(self.specs)


def _tree_map_with_path(fn, tree, path=()):
    if isinstance(tree, pm.ParamSpec):
        return fn(path, tree)
    return {k: _tree_map_with_path(fn, v, path + (k,))
            for k, v in tree.items()}
