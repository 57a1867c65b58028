"""Model assembly: per-arch segment plans, specs, forward/prefill/decode.

Every architecture is a sequence of *segments*; each segment is a
``lax.scan`` over stacked layer parameters (compact HLO, O(1) compile cost in
depth). Heterogeneous patterns (gemma3 5:1 local:global, zamba2 6-mamba +
shared-attention groups, xlstm 7 mLSTM + 1 sLSTM groups, deepseek 3 dense +
58 MoE) become nested scans over group-stacked parameters.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models import kvcache
from repro.models.attention import (attention_specs, attn_decode,
                                    attn_decode_cross, attn_forward,
                                    mla_decode, mla_forward, mla_specs)
from repro.models.context import MCtx
from repro.models.layers import (chunked_ce_loss, embed_tokens,
                                 embedding_specs, mlp_apply, mlp_specs,
                                 rmsnorm, rmsnorm_spec, sinusoidal_pos_emb,
                                 unembed)
from repro.models.moe import moe_ffn, moe_specs, use_ep
from repro.models.params import ParamSpec, stack_specs
from repro.models.ssm import ssm_decode, ssm_forward, ssm_specs
from repro.models.xlstm import (mlstm_decode, mlstm_forward, mlstm_specs,
                                slstm_decode, slstm_forward, slstm_specs)

AUX0 = jnp.zeros((), jnp.float32)


# --------------------------------------------------------------------------
# Segment plans
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Seg:
    name: str
    kind: str          # attn | gemma | zamba | mamba | xlstm
    n: int             # scan length (layers or groups)
    sub: int = 0       # inner group size (gemma locals / zamba mambas / mlstms)
    moe: bool = False
    window: int = 0


def segment_plan(cfg: ModelConfig) -> list[Seg]:
    if cfg.family == "hybrid":                      # zamba2
        n_groups = cfg.num_layers // cfg.attn_every
        tail = cfg.num_layers - n_groups * cfg.attn_every
        segs = [Seg("groups", "zamba", n_groups, sub=cfg.attn_every)]
        if tail:
            segs.append(Seg("tail", "mamba", tail))
        return segs
    if cfg.family == "ssm":                         # xlstm
        n_groups = cfg.num_layers // cfg.slstm_every
        tail = cfg.num_layers - n_groups * cfg.slstm_every
        segs = [Seg("groups", "xlstm", n_groups, sub=cfg.slstm_every - 1)]
        if tail:
            segs.append(Seg("tail", "xlstm_tail", tail))
        return segs
    if cfg.attn_type == "local_global":             # gemma3
        g = cfg.local_global_ratio + 1
        n_groups = cfg.num_layers // g
        tail = cfg.num_layers - n_groups * g
        segs = [Seg("groups", "gemma", n_groups, sub=cfg.local_global_ratio,
                    window=cfg.window)]
        if tail:
            segs.append(Seg("tail", "attn", tail, window=cfg.window))
        return segs
    if cfg.moe is not None:
        segs = []
        fd = cfg.moe.first_dense_layers
        if fd:
            segs.append(Seg("dense", "attn", fd, window=cfg.window
                            if cfg.attn_type == "swa" else 0))
        segs.append(Seg("moe", "attn", cfg.num_layers - fd, moe=True,
                        window=cfg.window if cfg.attn_type == "swa" else 0))
        return segs
    window = cfg.window if cfg.attn_type == "swa" else 0
    return [Seg("decoder", "attn", cfg.num_layers, window=window)]


# --------------------------------------------------------------------------
# Block specs
# --------------------------------------------------------------------------


def attn_block_specs(cfg: ModelConfig, moe: bool, ep: bool,
                     cross: bool = False, gated: bool = True) -> dict:
    d = cfg.d_model
    specs: dict[str, Any] = {"ln1": rmsnorm_spec(d)}
    specs["attn"] = (mla_specs(cfg) if cfg.attn_type == "mla"
                     else attention_specs(cfg))
    if cross:
        specs["ln_x"] = rmsnorm_spec(d)
        specs["xattn"] = attention_specs(cfg)
    specs["ln2"] = rmsnorm_spec(d)
    if moe:
        specs["moe"] = moe_specs(cfg, ep)
    else:
        specs["mlp"] = mlp_specs(d, cfg.d_ff, gated=gated)
    return specs


def mamba_block_specs(cfg: ModelConfig) -> dict:
    return {"ln": rmsnorm_spec(cfg.d_model), "ssm": ssm_specs(cfg)}


def shared_attn_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    return {"ln1": rmsnorm_spec(d), "attn": attention_specs(cfg),
            "ln2": rmsnorm_spec(d), "mlp": mlp_specs(d, cfg.d_ff)}


def mlstm_block_specs(cfg: ModelConfig) -> dict:
    return {"ln": rmsnorm_spec(cfg.d_model), "cell": mlstm_specs(cfg)}


def slstm_block_specs(cfg: ModelConfig) -> dict:
    return {"ln": rmsnorm_spec(cfg.d_model), "cell": slstm_specs(cfg)}


def seg_specs(cfg: ModelConfig, seg: Seg, ep: bool) -> dict:
    if seg.kind == "attn":
        return stack_specs(attn_block_specs(cfg, seg.moe, ep), seg.n)
    if seg.kind == "gemma":
        return stack_specs({
            "local": stack_specs(attn_block_specs(cfg, False, ep), seg.sub),
            "global": attn_block_specs(cfg, False, ep),
        }, seg.n)
    if seg.kind == "zamba":
        return stack_specs({
            "mamba": stack_specs(mamba_block_specs(cfg), seg.sub),
        }, seg.n)
    if seg.kind == "mamba":
        return stack_specs(mamba_block_specs(cfg), seg.n)
    if seg.kind == "xlstm":
        return stack_specs({
            "mlstm": stack_specs(mlstm_block_specs(cfg), seg.sub),
            "slstm": slstm_block_specs(cfg),
        }, seg.n)
    if seg.kind == "xlstm_tail":
        return stack_specs(mlstm_block_specs(cfg), seg.n)
    raise ValueError(seg.kind)


def model_specs(cfg: ModelConfig, mesh) -> dict:
    """Full parameter spec tree for an architecture."""
    ep = use_ep(cfg, mesh) if cfg.moe is not None else False
    specs: dict[str, Any] = {"embed": embedding_specs(cfg),
                             "final_norm": rmsnorm_spec(cfg.d_model)}
    if cfg.encoder_decoder:
        specs["encoder"] = stack_specs(
            attn_block_specs(cfg, False, ep, gated=False),
            cfg.num_encoder_layers)
        specs["enc_norm"] = rmsnorm_spec(cfg.d_model)
        specs["decoder"] = stack_specs(
            attn_block_specs(cfg, False, ep, cross=True, gated=False),
            cfg.num_layers)
        return specs
    for seg in segment_plan(cfg):
        specs[seg.name] = seg_specs(cfg, seg, ep)
    if cfg.family == "hybrid":
        specs["shared_attn"] = shared_attn_specs(cfg)
    return specs


# --------------------------------------------------------------------------
# Block applies (forward)
# --------------------------------------------------------------------------


def _attn_block_fwd(p, x, positions, cfg: ModelConfig, mctx: MCtx, *,
                    window: int, moe: bool, causal: bool = True,
                    use_rope: bool = True, collect: bool, gated: bool = True,
                    q_chunk: int = 512):
    # Megatron-SP pattern (§Perf A2): the residual stream between blocks is
    # seq-sharded over 'model'; gather the sequence at block entry and
    # reduce-scatter back at exit. Without these explicit points GSPMD
    # resolves the seq/hidden conflict by gathering WHOLE weights over both
    # axes — no tensor parallelism at all (16x flops, replicated grads).
    sp_in = ("act_batch", None, None)         # seq gathered, TP inside
    sp_out = ("act_batch", "act_seq", "act_embed")
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    h = mctx.constrain(h, sp_in)
    with jax.named_scope("attn"):
        if cfg.attn_type == "mla":
            a, kv = mla_forward(p["attn"], h, positions, cfg,
                                q_chunk=q_chunk)
        else:
            a, kv = attn_forward(p["attn"], h, positions, cfg,
                                 causal=causal, window=window,
                                 use_rope=use_rope, q_chunk=q_chunk,
                                 mctx=mctx)
    a = mctx.constrain(a, sp_out)
    x = x + a
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if moe:
        f, aux = moe_ffn(p["moe"], h2, cfg, mctx)
    else:
        with jax.named_scope("mlp"):
            h2 = mctx.constrain(h2, sp_in)
            f, aux = mlp_apply(p["mlp"], h2, gated=gated, mctx=mctx), AUX0
            f = mctx.constrain(f, sp_out)
    x = x + f
    if not collect:
        kv = None
    return x, kv, aux


def _mamba_block_fwd(p, x, cfg, collect: bool):
    out, cache = ssm_forward(p["ssm"], rmsnorm(x, p["ln"], cfg.norm_eps),
                             cfg)
    return x + out, (cache if collect else None)


def _mlstm_block_fwd(p, x, cfg, collect: bool):
    out, cache = mlstm_forward(p["cell"], rmsnorm(x, p["ln"], cfg.norm_eps),
                               cfg)
    return x + out, (cache if collect else None)


def _slstm_block_fwd(p, x, cfg, collect: bool):
    out, cache = slstm_forward(p["cell"], rmsnorm(x, p["ln"], cfg.norm_eps),
                               cfg)
    return x + out, (cache if collect else None)


def _to_ring(kv: Optional[dict], window: int, S: int):
    """Convert full-length rope'd K/V into ring-cache layout (slot=pos%W)."""
    if kv is None or window <= 0 or S <= window:
        return kv
    def conv(a):
        last = a[:, S - window:]
        return jnp.roll(last, shift=S % window, axis=1)
    return {k: conv(v) for k, v in kv.items()}


def _maybe_remat(fn, enable: bool):
    return jax.checkpoint(fn) if enable else fn


# --------------------------------------------------------------------------
# Segment applies (forward)
# --------------------------------------------------------------------------


def _cast_cache(kv, mctx: MCtx):
    # caches keep the model compute dtype (bf16 in production configs)
    if kv is None:
        return None
    return mctx.constrain_kv(dict(kv))


def seg_forward(p, x, positions, cfg: ModelConfig, mctx: MCtx, seg: Seg, *,
                collect: bool, remat: bool, shared_attn=None,
                q_chunk: int = 512):
    S = x.shape[1]

    if seg.kind == "attn":
        block = partial(_attn_block_fwd, positions=positions, cfg=cfg,
                        mctx=mctx, window=seg.window, moe=seg.moe,
                        collect=collect, q_chunk=q_chunk)
        body = _maybe_remat(block, remat)

        def f(carry, p_l):
            x, aux = carry
            x, kv, a = body(p_l, x)
            with jax.named_scope("attn/kv_update"):
                kv = _cast_cache(_to_ring(kv, seg.window, S), mctx)
            return (x, aux + a), kv
        (x, aux), caches = jax.lax.scan(f, (x, AUX0), p)
        return x, caches, aux

    if seg.kind == "gemma":
        # remat is per-BLOCK (not per-group): group-level recompute would
        # keep all 6 layers' intermediates live during the group backward.
        local_blk = _maybe_remat(
            partial(_attn_block_fwd, positions=positions, cfg=cfg,
                    mctx=mctx, window=seg.window, moe=False,
                    collect=collect, q_chunk=q_chunk), remat)
        global_blk = _maybe_remat(
            partial(_attn_block_fwd, positions=positions, cfg=cfg,
                    mctx=mctx, window=0, moe=False, collect=collect,
                    q_chunk=q_chunk), remat)

        def group(carry, p_g):
            x, aux = carry

            def local_f(c, p_l):
                xx, au = c
                xx, kv, a = local_blk(p_l, xx)
                return (xx, au + a), _cast_cache(
                    _to_ring(kv, seg.window, S), mctx)
            (x, aux), local_kv = jax.lax.scan(local_f, (x, aux), p_g["local"])
            x, gkv, a = global_blk(p_g["global"], x)
            return (x, aux + a), {"local": local_kv,
                                  "global": _cast_cache(gkv, mctx)}
        (x, aux), caches = jax.lax.scan(group, (x, AUX0), p)
        return x, caches, aux

    if seg.kind == "zamba":
        mamba_blk = _maybe_remat(
            partial(_mamba_block_fwd, cfg=cfg, collect=collect), remat)

        def shared_blk(sa, x):
            h = rmsnorm(x, sa["ln1"], cfg.norm_eps)
            a, kv = attn_forward(sa["attn"], h, positions, cfg, causal=True,
                                 q_chunk=q_chunk)
            x = x + a
            x = x + mlp_apply(sa["mlp"],
                              rmsnorm(x, sa["ln2"], cfg.norm_eps))
            return x, kv
        shared_blk_r = _maybe_remat(shared_blk, remat)

        def group(carry, p_g):
            x, aux = carry

            def mam(c, p_l):
                xx, _ = c
                xx, cache = mamba_blk(p_l, xx)
                return (xx, AUX0), cache
            (x, _), mcaches = jax.lax.scan(mam, (x, AUX0), p_g["mamba"])
            # shared attention block (single weight copy, captured)
            x, kv = shared_blk_r(shared_attn, x)
            return (x, aux), {"mamba": mcaches,
                              "attn": _cast_cache(kv if collect else None,
                                                  mctx)}
        (x, aux), caches = jax.lax.scan(group, (x, AUX0), p)
        return x, caches, aux

    if seg.kind == "mamba":
        def f(carry, p_l):
            x, aux = carry
            x, cache = _mamba_block_fwd(p_l, x, cfg, collect)
            return (x, aux), cache
        body = _maybe_remat(f, remat)
        (x, aux), caches = jax.lax.scan(body, (x, AUX0), p)
        return x, caches, aux

    if seg.kind == "xlstm":
        ml_blk = _maybe_remat(
            partial(_mlstm_block_fwd, cfg=cfg, collect=collect), remat)
        sl_blk = _maybe_remat(
            partial(_slstm_block_fwd, cfg=cfg, collect=collect), remat)

        def group(carry, p_g):
            x, aux = carry

            def ml(c, p_l):
                xx, _ = c
                xx, cache = ml_blk(p_l, xx)
                return (xx, AUX0), cache
            (x, _), mcaches = jax.lax.scan(ml, (x, AUX0), p_g["mlstm"])
            x, scache = sl_blk(p_g["slstm"], x)
            return (x, aux), {"mlstm": mcaches, "slstm": scache}
        (x, aux), caches = jax.lax.scan(group, (x, AUX0), p)
        return x, caches, aux

    if seg.kind == "xlstm_tail":
        def f(carry, p_l):
            x, aux = carry
            x, cache = _mlstm_block_fwd(p_l, x, cfg, collect)
            return (x, aux), cache
        body = _maybe_remat(f, remat)
        (x, aux), caches = jax.lax.scan(body, (x, AUX0), p)
        return x, caches, aux

    raise ValueError(seg.kind)


# --------------------------------------------------------------------------
# Top-level forward / loss
# --------------------------------------------------------------------------


def _input_hidden(params, cfg: ModelConfig, batch: dict, dtype):
    if cfg.frontend in ("vision", "audio") and "embeds" in batch:
        return batch["embeds"].astype(dtype)
    return embed_tokens(params["embed"], batch["tokens"], dtype)


def _positions(cfg: ModelConfig, batch: dict, B: int, S: int):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def forward_hidden(params, cfg: ModelConfig, mctx: MCtx, batch: dict, *,
                   collect: bool = False, remat: bool = False,
                   q_chunk: int = 512):
    """Returns (hidden (B,S,d), caches, aux). Decoder-only archs."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = _input_hidden(params, cfg, batch, dtype)
    B, S = x.shape[:2]
    positions = _positions(cfg, batch, B, S)
    x = mctx.constrain(x, ("act_batch", "act_seq", "act_embed"))
    caches = {}
    aux = AUX0
    shared = params.get("shared_attn")
    for seg in segment_plan(cfg):
        x, c, a = seg_forward(params[seg.name], x, positions, cfg, mctx, seg,
                              collect=collect, remat=remat,
                              shared_attn=shared, q_chunk=q_chunk)
        x = mctx.constrain(x, ("act_batch", "act_seq", "act_embed"))
        caches[seg.name] = c
        aux = aux + a
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, caches, aux


def encdec_forward(params, cfg: ModelConfig, mctx: MCtx, batch: dict, *,
                   collect: bool = False, remat: bool = False,
                   q_chunk: int = 512):
    """Whisper-style enc-dec. batch: frames (B,S_enc,d), tokens (B,S_dec)."""
    dtype = jnp.dtype(cfg.dtype)
    frames = batch["frames"].astype(dtype)
    B, S_enc = frames.shape[:2]
    enc_x = frames + sinusoidal_pos_emb(jnp.arange(S_enc),
                                        cfg.d_model).astype(dtype)
    enc_pos = jnp.broadcast_to(jnp.arange(S_enc)[None], (B, S_enc))

    def enc_f(carry, p_l):
        x, _ = carry
        x, _, _ = _attn_block_fwd(p_l, x, enc_pos, cfg, mctx, window=0,
                                  moe=False, causal=False, use_rope=False,
                                  collect=False, gated=False,
                                  q_chunk=q_chunk)
        return (x, AUX0), None
    enc_body = _maybe_remat(enc_f, remat)
    (enc_x, _), _ = jax.lax.scan(enc_body, (enc_x, AUX0), params["encoder"])
    enc_out = rmsnorm(enc_x, params["enc_norm"], cfg.norm_eps)

    tokens = batch["tokens"]
    S_dec = tokens.shape[1]
    x = embed_tokens(params["embed"], tokens, dtype)
    x = x + sinusoidal_pos_emb(jnp.arange(S_dec), cfg.d_model).astype(dtype)
    dec_pos = jnp.broadcast_to(jnp.arange(S_dec)[None], (B, S_dec))

    def dec_f(carry, p_l):
        x, _ = carry
        h = rmsnorm(x, p_l["ln1"], cfg.norm_eps)
        a, kv = attn_forward(p_l["attn"], h, dec_pos, cfg, causal=True,
                             use_rope=False, q_chunk=q_chunk)
        x = x + a
        hx = rmsnorm(x, p_l["ln_x"], cfg.norm_eps)
        cx, xkv = attn_forward(p_l["xattn"], hx, dec_pos, cfg, causal=False,
                               use_rope=False, x_kv=enc_out,
                               kv_positions=enc_pos, q_chunk=q_chunk)
        x = x + cx
        f = mlp_apply(p_l["mlp"], rmsnorm(x, p_l["ln2"], cfg.norm_eps),
                      gated=False)
        x = x + f
        caches = ({"self": _cast_cache(kv, mctx),
                   "cross": _cast_cache(xkv, mctx)} if collect else None)
        return (x, AUX0), caches
    dec_body = _maybe_remat(dec_f, remat)
    (x, _), caches = jax.lax.scan(dec_body, (x, AUX0), params["decoder"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, caches, AUX0


def loss_fn(params, cfg: ModelConfig, mctx: MCtx, batch: dict,
            aux_coef: float = 0.001, q_chunk: int = 512):
    remat = mctx.parallel.remat != "none"
    if cfg.encoder_decoder:
        x, _, aux = encdec_forward(params, cfg, mctx, batch, remat=remat,
                                   q_chunk=q_chunk)
    else:
        x, _, aux = forward_hidden(params, cfg, mctx, batch, remat=remat,
                                   q_chunk=q_chunk)
    ce = chunked_ce_loss(x, params["embed"], batch["labels"],
                         cfg.tie_embeddings)
    return ce + aux_coef * aux, {"ce": ce, "aux": aux}
