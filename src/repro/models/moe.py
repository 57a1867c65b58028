"""Mixture-of-Experts FFN with two distribution strategies.

* **EP** (expert parallelism): experts sharded over the combined
  ``(data, model)`` axes (DeepSeek-V3: 256 experts over 256 chips -> 1
  expert/chip). Token dispatch is an explicit ``all_to_all`` inside
  ``shard_map`` — the canonical DeepSeek/GShard EP schedule. Used when
  ``num_experts % (data*model) == 0``.
* **TP** (tensor parallelism): every chip holds all experts with the FFN
  hidden dim sharded over ``model`` and the embed dim FSDP-sharded over
  ``data`` (Mixtral: 8 experts < 256 chips). Dispatch is chip-local; one
  psum over ``model`` combines partial outputs (the standard TP
  all-reduce).

Both paths use capacity-based top-k routing with sort-based dispatch
(never materializing a (T, E, C) one-hot) and drop overflow tokens
(GShard-style; capacity_factor controls the overhead, which is reported in
the roofline MODEL_FLOPS/HLO_FLOPs ratio).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config.base import ModelConfig
from repro.models.params import ParamSpec


# --------------------------------------------------------------------------
# Specs
# --------------------------------------------------------------------------


def use_ep(cfg: ModelConfig, mesh) -> bool:
    e = cfg.moe
    group = mesh.shape.get("data", 1) * mesh.shape.get("model", 1)
    return e.num_experts % group == 0 and e.num_experts >= group


def moe_specs(cfg: ModelConfig, ep: bool) -> dict:
    e = cfg.moe
    d = cfg.d_model
    ff = e.d_ff_expert or cfg.d_ff
    waxes = (("experts", None, None) if ep else (None, "embed", "mlp"))
    daxes = (("experts", None, None) if ep else (None, "mlp", "embed"))
    specs = {
        "router": ParamSpec((d, e.num_experts), (None, None),
                            init="small_normal"),
        "w_gate": ParamSpec((e.num_experts, d, ff), waxes),
        "w_up": ParamSpec((e.num_experts, d, ff), waxes),
        "w_down": ParamSpec((e.num_experts, ff, d), daxes),
    }
    if e.num_shared_experts:
        ffs = ff * e.num_shared_experts
        specs["shared"] = {
            "w_gate": ParamSpec((d, ffs), ("embed", "mlp")),
            "w_up": ParamSpec((d, ffs), ("embed", "mlp")),
            "w_down": ParamSpec((ffs, d), ("mlp", "embed")),
        }
    return specs


# --------------------------------------------------------------------------
# Routing / dispatch helpers (chip-local; used inside shard_map)
# --------------------------------------------------------------------------


def _route(x, router_w, k: int):
    """x: (T, d) -> gates (T, k) f32, eids (T, k) i32, probs (T, E) f32."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eids, probs


def _aux_loss(probs, eids, E: int):
    """Switch-style load-balancing loss (chip-local mean)."""
    T, k = eids.shape
    hits = jax.nn.one_hot(eids, E, dtype=jnp.float32).sum(1)   # (T, E)
    frac_tokens = hits.mean(0) / k
    frac_probs = probs.mean(0)
    return E * jnp.sum(frac_tokens * frac_probs)


def _dispatch_indices(eids, E: int, C: int):
    T, k = eids.shape
    flat_e = eids.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = order // k
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[se]
    keep = pos < C
    pos_safe = jnp.where(keep, pos, C)       # C is out-of-bounds -> dropped
    return se, st, pos_safe, keep, order


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    c = int(math.ceil(T * k * cf / E))
    return max(4, -(-c // 4) * 4)            # round up to multiple of 4


def _expert_ffn(toks, w_gate, w_up, w_down):
    """toks: (E, C, d); weights (E, d, ff)/(E, ff, d)."""
    dt = toks.dtype
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", toks, w_gate.astype(dt)))
         * jnp.einsum("ecd,edf->ecf", toks, w_up.astype(dt)))
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))


# --------------------------------------------------------------------------
# EP path (experts over (data, model); all_to_all dispatch)
# --------------------------------------------------------------------------


def _moe_ep_body(x, router_w, w_gate, w_up, w_down, *, cfg: ModelConfig,
                 group_axes: tuple[str, ...], tp_axis: str,
                 all_axes: tuple[str, ...]):
    e = cfg.moe
    E = e.num_experts
    B, S, d = x.shape
    tp = jax.lax.axis_size(tp_axis)
    G = 1
    for a in group_axes:
        G *= jax.lax.axis_size(a)
    E_loc = E // G
    T_loc = B * S
    x_tok = x.reshape(T_loc, d)
    # Split tokens over the model axis so routing/dispatch work is TP-sharded.
    T_pad = -(-T_loc // tp) * tp
    if T_pad != T_loc:
        x_tok = jnp.pad(x_tok, ((0, T_pad - T_loc), (0, 0)))
    T_chip = T_pad // tp
    j = jax.lax.axis_index(tp_axis)
    x_my = jax.lax.dynamic_slice_in_dim(x_tok, j * T_chip, T_chip, axis=0)

    gates, eids, probs = _route(x_my, router_w, e.top_k)
    aux = _aux_loss(probs, eids, E)
    C = _capacity(T_chip, e.top_k, E, e.capacity_factor)
    se, st, pos, keep, order = _dispatch_indices(eids, E, C)
    buf = jnp.zeros((E, C, d), x.dtype).at[se, pos].set(
        x_my[st], mode="drop")

    # all_to_all: (G, E_loc, C, d) -> every chip receives its experts' slices
    send = buf.reshape(G, E_loc, C, d)
    recv = jax.lax.all_to_all(send, group_axes, split_axis=0, concat_axis=0)
    toks = recv.transpose(1, 0, 2, 3).reshape(E_loc, G * C, d)

    out_toks = _expert_ffn(toks, w_gate, w_up, w_down)

    back = out_toks.reshape(E_loc, G, C, d).transpose(1, 0, 2, 3)
    out_buf = jax.lax.all_to_all(back, group_axes, split_axis=0,
                                 concat_axis=0).reshape(E, C, d)

    vals = out_buf.at[se, pos].get(mode="fill", fill_value=0)
    w = (gates.reshape(-1)[order] * keep).astype(x.dtype)
    y_my = jnp.zeros((T_chip, d), x.dtype).at[st].add(vals * w[:, None])

    y = jax.lax.all_gather(y_my, tp_axis, axis=0, tiled=True)   # (T_pad, d)
    y = y[:T_loc].reshape(B, S, d)
    aux = jax.lax.pmean(aux, all_axes)
    return y, aux


# --------------------------------------------------------------------------
# TP path (experts replicated, ff sharded over model; local dispatch)
# --------------------------------------------------------------------------


def _moe_tp_body(x, router_w, w_gate, w_up, w_down, *, cfg: ModelConfig,
                 fsdp_axis, tp_axis: str, n_chunks: int,
                 all_axes: tuple[str, ...]):
    e = cfg.moe
    E = e.num_experts
    B, S, d = x.shape
    if fsdp_axis is not None:
        # FSDP all-gather of the expert weights (bf16) for this layer.
        w_gate = jax.lax.all_gather(w_gate.astype(x.dtype), fsdp_axis,
                                    axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up.astype(x.dtype), fsdp_axis,
                                  axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down.astype(x.dtype), fsdp_axis,
                                    axis=2, tiled=True)
    T_loc = B * S
    x_tok = x.reshape(T_loc, d)
    nc = n_chunks if T_loc % n_chunks == 0 else 1
    Tc = T_loc // nc
    C = _capacity(Tc, e.top_k, E, e.capacity_factor)

    def one(x_c):
        gates, eids, probs = _route(x_c, router_w, e.top_k)
        aux = _aux_loss(probs, eids, E)
        se, st, pos, keep, order = _dispatch_indices(eids, E, C)
        buf = jnp.zeros((E, C, d), x.dtype).at[se, pos].set(
            x_c[st], mode="drop")
        out_buf = _expert_ffn(buf, w_gate, w_up, w_down)
        vals = out_buf.at[se, pos].get(mode="fill", fill_value=0)
        w = (gates.reshape(-1)[order] * keep).astype(x.dtype)
        y = jnp.zeros((Tc, d), x.dtype).at[st].add(vals * w[:, None])
        return y, aux

    if nc == 1:
        y, aux = one(x_tok)
    else:
        def body(_, x_c):
            return None, one(x_c)
        _, (ys, auxs) = jax.lax.scan(body, None,
                                     x_tok.reshape(nc, Tc, d))
        y, aux = ys.reshape(T_loc, d), auxs.mean()
    # ff was model-sharded -> partial sums; the TP all-reduce:
    y = jax.lax.psum(y, tp_axis)
    aux = jax.lax.pmean(aux, all_axes)
    return y.reshape(B, S, d), aux


# --------------------------------------------------------------------------
# Public entry
# --------------------------------------------------------------------------


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig, mctx) -> tuple:
    """x: (B, S, d) (batch sharded over mctx.batch_axes). Returns (y, aux)."""
    e = cfg.moe
    mesh = mctx.mesh
    ep = use_ep(cfg, mesh)
    batch_axes = mctx.batch_axes
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    if batch_axes and x.shape[0] % bsz == 0:
        x_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
                   None, None)
    else:
        # tiny batches (long-context decode, B=1): replicate over batch axes
        x_spec = P(None, None, None)
    group_axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)

    all_axes = tuple(mesh.axis_names)
    if ep:
        body = partial(_moe_ep_body, cfg=cfg, group_axes=group_axes,
                       tp_axis="model", all_axes=all_axes)
        in_specs = (x_spec, P(None, None),
                    P(group_axes, None, None),
                    P(group_axes, None, None),
                    P(group_axes, None, None))
    else:
        fsdp = "data" if (mctx.parallel.fsdp and "data" in mesh.axis_names
                          ) else None
        body = partial(_moe_tp_body, cfg=cfg, fsdp_axis=fsdp,
                       tp_axis="model", n_chunks=8, all_axes=all_axes)
        in_specs = (x_spec, P(None, None),
                    P(None, fsdp, "model"),
                    P(None, fsdp, "model"),
                    P(None, "model", fsdp))

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=(x_spec, P()), check_vma=False)
    y, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if e.num_shared_experts:
        sp = p["shared"]
        dt = x.dtype
        h = (jax.nn.silu(x @ sp["w_gate"].astype(dt))
             * (x @ sp["w_up"].astype(dt)))
        y = y + h @ sp["w_down"].astype(dt)
    return y, aux
