"""Train-step builder: grads -> (optionally compressed) reduction -> AdamW.

The returned step is pure and jit-ready; tier placement is expressed through
the shardings of its inputs/outputs (see repro.core.offload.state_shardings),
so the same function lowers for the dry-run and runs for real.

Beyond-paper option: ``compress_pod_grads`` wraps the loss in a shard_map
manual over the 'pod' axis and replaces the cross-pod bf16 gradient
all-reduce with an int8 all_gather + local mean (error-feedback-free variant;
the EF variant lives in repro.core.compression for the optimizer hook).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compression import compressed_pod_mean
from repro.models.context import MCtx
from repro.models.model import Model
from repro.models.transformer import loss_fn
from repro.optim import adamw
from repro.launch.mesh import POD_AXIS


def _batch_pod_specs(batch: dict) -> dict:
    """Per-key pod in_specs (batch dim may not be dim 0, e.g. positions)."""
    specs = {}
    for k, v in batch.items():
        if k == "positions":
            specs[k] = P(None, POD_AXIS)
        else:
            specs[k] = P(POD_AXIS)
    return specs


def compute_grads(model: Model, params_c, batch,
                  compress_pod_grads: bool = False):
    """Returns ((loss, parts), grads)."""
    cfg, mctx = model.cfg, model.mctx
    mesh = mctx.mesh
    use_pod = compress_pod_grads and POD_AXIS in mesh.axis_names

    if not use_pod:
        def lf(p):
            return loss_fn(p, cfg, mctx, batch)
        return jax.value_and_grad(lf, has_aux=True)(params_c)

    inner_mctx = MCtx(mesh, mctx.parallel,
                      seq_sharded_cache=mctx.seq_sharded_cache,
                      manual_pod=True)

    def body(params, batch):
        def lf(p):
            return loss_fn(p, cfg, inner_mctx, batch)
        (loss, parts), grads = jax.value_and_grad(lf, has_aux=True)(params)
        grads = jax.tree.map(partial(compressed_pod_mean,
                                     pod_axis=POD_AXIS), grads)
        loss = jax.lax.pmean(loss, POD_AXIS)
        parts = jax.tree.map(lambda x: jax.lax.pmean(x, POD_AXIS), parts)
        return (loss, parts), grads

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), _batch_pod_specs(batch)),
                       out_specs=((P(), P()), P()),
                       axis_names=frozenset({POD_AXIS}),
                       check_vma=False)
    return fn(params_c, batch)


def _split_microbatches(batch: dict, n: int) -> dict:
    """Reshape every batch leaf to (n, B/n, ...) on its batch dim."""
    out = {}
    for k, v in batch.items():
        ax = 1 if k == "positions" else 0
        B = v.shape[ax]
        assert B % n == 0, f"{k}: batch {B} % microbatches {n}"
        new = v.reshape(v.shape[:ax] + (n, B // n) + v.shape[ax + 1:])
        out[k] = jnp.moveaxis(new, ax, 0) if ax else new
    return out


def _device_shardings(model: Model):
    from repro.models.params import ParamSpec
    return jax.tree.map(lambda s: model.param_sharding(s, "device"),
                        model.specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def make_train_step(model: Model, hyper: adamw.AdamWConfig,
                    lr_fn: Callable, compress_pod_grads: bool = False,
                    offload_plan=None):
    """step(params_c, master, opt_state, batch) ->
    (params_c, master, opt_state, metrics).

    With parallel.microbatches > 1, gradients accumulate in fp32 over a
    lax.scan of microbatches (live activations shrink by the same factor).

    With an offload placement plan, host-resident state groups (master /
    mu / nu in pinned_host, the paper's §6.1.5 mode) are transferred to
    device memory for the update and written back host-side by the step's
    out_shardings — XLA schedules the PCIe traffic, which the cost model
    (repro.core.costmodel) budgets against the link bandwidth."""
    n_micro = model.mctx.parallel.microbatches
    kinds = offload_plan.memory_kinds() if offload_plan else {}
    any_offload = any(v != "device" for v in kinds.values())
    dev_sh = _device_shardings(model) if any_offload else None

    def to_device(tree, group):
        if dev_sh is None or kinds.get(group, "device") == "device":
            return tree
        return jax.tree.map(jax.device_put, tree, dev_sh)

    def to_home(tree, group):
        """Write offloaded state back to its home tier (in-body device_put;
        out_shardings with memory kinds trips an XLA SPMD RET_CHECK)."""
        kind = kinds.get(group, "device")
        if dev_sh is None or kind == "device":
            return tree
        from repro.models.params import ParamSpec
        home = jax.tree.map(lambda s: model.param_sharding(s, kind),
                            model.specs,
                            is_leaf=lambda x: isinstance(x, ParamSpec))
        return jax.tree.map(jax.device_put, tree, home)

    def grads_of(params_c, batch):
        return compute_grads(model, params_c, batch,
                             compress_pod_grads=compress_pod_grads)

    def step(params_c, master, opt_state: adamw.OptState, batch):
        if n_micro > 1:
            mbs = _split_microbatches(batch, n_micro)

            def body(carry, mb):
                acc, loss_s, ce_s, aux_s = carry
                (loss, parts), grads = grads_of(params_c, mb)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), acc, grads)
                return (acc, loss_s + loss, ce_s + parts["ce"],
                        aux_s + parts["aux"]), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params_c)
            (acc, loss, ce, aux), _ = jax.lax.scan(
                body, (zeros, 0.0, 0.0, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / n_micro, acc)
            loss, ce, aux = loss / n_micro, ce / n_micro, aux / n_micro
            parts = {"ce": ce, "aux": aux}
        else:
            (loss, parts), grads = grads_of(params_c, batch)
        lr = lr_fn(opt_state.count)
        master = to_device(master, "master")
        opt_state = adamw.OptState(mu=to_device(opt_state.mu, "mu"),
                                   nu=to_device(opt_state.nu, "nu"),
                                   count=opt_state.count)
        master2, params_c2, opt_state2, gnorm = adamw.update(
            grads, opt_state, master, lr, hyper)
        master2 = to_home(master2, "master")
        opt_state2 = adamw.OptState(mu=to_home(opt_state2.mu, "mu"),
                                    nu=to_home(opt_state2.nu, "nu"),
                                    count=opt_state2.count)
        metrics = {"loss": loss, "ce": parts["ce"], "aux": parts["aux"],
                   "grad_norm": gnorm, "lr": lr}
        return params_c2, master2, opt_state2, metrics

    return step


def init_train_state(model: Model, rng):
    """(params_c bf16, master fp32, opt_state)."""
    master = model.init(rng)
    params_c = jax.tree.map(lambda p: p.astype(jnp.bfloat16), master)
    return params_c, master, adamw.init(master)


def abstract_train_state(model: Model, plan):
    """ShapeDtypeStruct trees for (params_c, master, opt_state) with the
    placement plan's memory kinds attached — dry-run inputs."""
    from repro.models.params import ParamSpec
    kinds = plan.memory_kinds()

    def sds_tree(dtype, kind):
        mk = None if kind == "device" else kind

        def one(s):
            return jax.ShapeDtypeStruct(
                s.shape, dtype, sharding=model.param_sharding(s, mk))
        return jax.tree.map(one, model.specs,
                            is_leaf=lambda x: isinstance(x, ParamSpec))

    params_c = sds_tree(jnp.bfloat16, kinds["params"])
    master = sds_tree(jnp.float32, kinds["master"])
    mu = sds_tree(jnp.float32, kinds["mu"])
    nu = sds_tree(jnp.float32, kinds["nu"])
    count = jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=jax.sharding.NamedSharding(
            model.mctx.mesh, P()))
    return params_c, master, adamw.OptState(mu=mu, nu=nu, count=count)
