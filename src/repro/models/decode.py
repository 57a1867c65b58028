"""Decode (serve) path: cache specs, prefill, single-token decode step.

Decode caches mirror the ``collect=True`` structure of the forward pass, so
prefill output feeds decode directly. For ``long_500k`` the attention caches
are sequence-sharded over the 'data' mesh axis (``mctx.seq_sharded_cache``)
and XLA partitions the score/softmax reductions flash-decoding style.

Each layer reads its attention cache once, inside the layer scan, and
returns only the new token's K/V row; ``write_rows`` writes the stacked
rows into the (donated) cache after the scan, so a step moves no
cache-sized buffer. MLA (``mla_decode``) still returns its latent cache.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models import kvcache
from repro.models.attention import (attn_decode, attn_decode_cross,
                                    decode_slot, mla_decode)
from repro.models.context import MCtx
from repro.models.layers import (embed_tokens, mlp_apply, rmsnorm,
                                 sinusoidal_pos_emb, unembed)
from repro.models.moe import moe_ffn
from repro.models.params import stack_specs
from repro.models.ssm import ssm_decode
from repro.models.transformer import (Seg, encdec_forward, forward_hidden,
                                      segment_plan)
from repro.models.xlstm import mlstm_decode, slstm_decode

WHISPER_CROSS_LEN = 1500   # 30 s of audio at the whisper frame rate


# --------------------------------------------------------------------------
# Cache specs (mirror forward collect structure)
# --------------------------------------------------------------------------


def _attn_cache(cfg, mctx, B, S, window):
    if cfg.attn_type == "mla":
        return kvcache.mla_cache_specs(cfg, B, S, mctx.cache_seq_axis)
    return kvcache.attn_cache_specs(cfg, B, S, mctx.cache_seq_axis,
                                    window=window)


def cache_specs(cfg: ModelConfig, mctx: MCtx, B: int, S: int) -> dict:
    """ParamSpec tree for the decode cache of (cfg, batch B, max len S)."""
    if cfg.encoder_decoder:
        layer = {"self": kvcache.attn_cache_specs(cfg, B, S, "act_seq"),
                 "cross": kvcache.cross_cache_specs(cfg, B,
                                                    WHISPER_CROSS_LEN)}
        return {"decoder": stack_specs(layer, cfg.num_layers)}
    out: dict[str, Any] = {}
    for seg in segment_plan(cfg):
        if seg.kind == "attn":
            out[seg.name] = stack_specs(
                _attn_cache(cfg, mctx, B, S, seg.window), seg.n)
        elif seg.kind == "gemma":
            out[seg.name] = stack_specs({
                "local": stack_specs(
                    _attn_cache(cfg, mctx, B, S, seg.window), seg.sub),
                "global": _attn_cache(cfg, mctx, B, S, 0),
            }, seg.n)
        elif seg.kind == "zamba":
            out[seg.name] = stack_specs({
                "mamba": stack_specs(kvcache.ssm_cache_specs(cfg, B),
                                     seg.sub),
                "attn": _attn_cache(cfg, mctx, B, S, 0),
            }, seg.n)
        elif seg.kind == "mamba":
            out[seg.name] = stack_specs(kvcache.ssm_cache_specs(cfg, B),
                                        seg.n)
        elif seg.kind == "xlstm":
            out[seg.name] = stack_specs({
                "mlstm": stack_specs(kvcache.mlstm_cache_specs(cfg, B),
                                     seg.sub),
                "slstm": kvcache.slstm_cache_specs(cfg, B),
            }, seg.n)
        elif seg.kind == "xlstm_tail":
            out[seg.name] = stack_specs(kvcache.mlstm_cache_specs(cfg, B),
                                        seg.n)
    return out


# --------------------------------------------------------------------------
# Block decode applies
# --------------------------------------------------------------------------


def _attn_block_dec(p, x, pos, cache, cfg, mctx, *, window, moe,
                    gated=True):
    """Returns (x, new K/V rows), or (x, whole latent cache) for MLA."""
    cache = mctx.constrain_kv(cache)      # keep seq-sharded inside the scan
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("attn"):
        if cfg.attn_type == "mla":
            a, cache = mla_decode(p["attn"], h, pos, cache, cfg)
            cache = mctx.constrain_kv(cache)
        else:
            a, cache = attn_decode(p["attn"], h, pos, cache, cfg,
                                   window=window)
    x = x + a
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if moe:
        f, _ = moe_ffn(p["moe"], h2, cfg, mctx)
    else:
        with jax.named_scope("mlp"):
            f = mlp_apply(p["mlp"], h2, gated=gated)
    return x + f, cache


def _mamba_block_dec(p, x, cache, cfg):
    out, cache = ssm_decode(p["ssm"], rmsnorm(x, p["ln"], cfg.norm_eps),
                            cache, cfg)
    return x + out, cache


def _mlstm_block_dec(p, x, cache, cfg):
    out, cache = mlstm_decode(p["cell"], rmsnorm(x, p["ln"], cfg.norm_eps),
                              cache, cfg)
    return x + out, cache


def _slstm_block_dec(p, x, cache, cfg):
    out, cache = slstm_decode(p["cell"], rmsnorm(x, p["ln"], cfg.norm_eps),
                              cache, cfg)
    return x + out, cache


# --------------------------------------------------------------------------
# Segment decode
# --------------------------------------------------------------------------


def write_rows(cache: dict, rows: dict, pos, window: int = 0) -> dict:
    """Write the new K/V rows (..., B, 1, Hkv, dh) into the stacked cache
    (..., B, S, Hkv, dh) at ``decode_slot``: the step's only cache write,
    in place when the cache is donated."""
    def put(c, r):
        start = [0] * c.ndim
        start[-3] = decode_slot(pos, c.shape[-3], window)
        return jax.lax.dynamic_update_slice(c, r, start)
    with jax.named_scope("attn/kv_update"):
        return {k: put(cache[k], rows[k]) for k in cache}


def seg_decode(p, cache, x, pos, cfg: ModelConfig, mctx: MCtx, seg: Seg,
               shared_attn=None):
    if seg.kind == "attn":
        def f(x, args):
            p_l, c_l = args
            return _attn_block_dec(p_l, x, pos, c_l, cfg, mctx,
                                   window=seg.window, moe=seg.moe)
        x, new = jax.lax.scan(f, x, (p, cache))
        if cfg.attn_type == "mla":
            return x, new
        return x, write_rows(cache, new, pos, seg.window)

    if seg.kind == "gemma":
        def group(x, args):
            p_g, c_g = args

            def loc(x, a):
                p_l, c_l = a
                return _attn_block_dec(p_l, x, pos, c_l, cfg, mctx,
                                       window=seg.window, moe=False)
            x, local_r = jax.lax.scan(loc, x, (p_g["local"], c_g["local"]))
            x, global_r = _attn_block_dec(p_g["global"], x, pos,
                                          c_g["global"], cfg, mctx,
                                          window=0, moe=False)
            return x, {"local": local_r, "global": global_r}
        x, rows = jax.lax.scan(group, x, (p, cache))
        return x, {"local": write_rows(cache["local"], rows["local"], pos,
                                       seg.window),
                   "global": write_rows(cache["global"], rows["global"],
                                        pos)}

    if seg.kind == "zamba":
        sa = shared_attn

        def group(x, args):
            p_g, c_g = args

            def mam(x, a):
                p_l, c_l = a
                return _mamba_block_dec(p_l, x, c_l, cfg)
            x, mcache = jax.lax.scan(mam, x, (p_g["mamba"], c_g["mamba"]))
            h = rmsnorm(x, sa["ln1"], cfg.norm_eps)
            a, kv = attn_decode(sa["attn"], h, pos,
                                mctx.constrain_kv(c_g["attn"]), cfg)
            x = x + a
            x = x + mlp_apply(sa["mlp"],
                              rmsnorm(x, sa["ln2"], cfg.norm_eps))
            return x, {"mamba": mcache, "attn": kv}
        x, new = jax.lax.scan(group, x, (p, cache))
        return x, {"mamba": new["mamba"],
                   "attn": write_rows(cache["attn"], new["attn"], pos)}

    if seg.kind == "mamba":
        def f(x, args):
            p_l, c_l = args
            return _mamba_block_dec(p_l, x, c_l, cfg)
        return jax.lax.scan(f, x, (p, cache))

    if seg.kind == "xlstm":
        def group(x, args):
            p_g, c_g = args

            def ml(x, a):
                p_l, c_l = a
                return _mlstm_block_dec(p_l, x, c_l, cfg)
            x, mcache = jax.lax.scan(ml, x, (p_g["mlstm"], c_g["mlstm"]))
            x, scache = _slstm_block_dec(p_g["slstm"], x, c_g["slstm"], cfg)
            return x, {"mlstm": mcache, "slstm": scache}
        return jax.lax.scan(group, x, (p, cache))

    if seg.kind == "xlstm_tail":
        def f(x, args):
            p_l, c_l = args
            return _mlstm_block_dec(p_l, x, c_l, cfg)
        return jax.lax.scan(f, x, (p, cache))

    raise ValueError(seg.kind)


# --------------------------------------------------------------------------
# Public: prefill + decode_step
# --------------------------------------------------------------------------


def _pad_caches_to(caches, cfg: ModelConfig, mctx: MCtx, B: int,
                   max_len: int):
    """Zero-pad collected prompt caches to the decode cache shapes.

    Prefill produces prompt-length KV; decode needs max_len-length buffers
    (ring caches pad to the window). Any axis mismatch vs cache_specs is
    padded at the end; ring validity masking handles the unwritten slots.
    """
    from repro.models.params import ParamSpec
    target = cache_specs(cfg, mctx, B, max_len)

    def pad(leaf, spec: ParamSpec):
        if leaf.shape == spec.shape:
            return leaf
        pads = []
        for have, want in zip(leaf.shape, spec.shape):
            assert want >= have, (leaf.shape, spec.shape)
            pads.append((0, want - have))
        return jnp.pad(leaf, pads)

    return jax.tree.map(pad, caches, target,
                        is_leaf=lambda x: not isinstance(x, dict))


def prefill(params, cfg: ModelConfig, mctx: MCtx, batch: dict,
            max_len: int = 0, q_chunk: int = 512):
    """Forward over the prompt; returns (last-token logits, caches).

    ``max_len`` sizes the decode cache buffers (0 -> prompt length; pass
    prompt+max_new_tokens for serving)."""
    if cfg.encoder_decoder:
        return _whisper_prefill(params, cfg, mctx, batch,
                                max_decode_len=max_len or 1024,
                                q_chunk=q_chunk)
    x, caches, _ = forward_hidden(params, cfg, mctx, batch, collect=True,
                                  q_chunk=q_chunk)
    B, S = x.shape[:2]
    if max_len and max_len > S:
        with jax.named_scope("attn/kv_update"):
            caches = _pad_caches_to(caches, cfg, mctx, B, max_len)
    with jax.named_scope("logits"):
        logits = unembed(params["embed"], x[:, -1:], cfg.tie_embeddings)
        logits = mctx.constrain(logits, ("act_batch", None, "act_vocab"))
    return logits, caches


def _whisper_prefill(params, cfg, mctx, batch, max_decode_len: int = 1024,
                     q_chunk: int = 512):
    """Encoder forward + per-layer cross-KV; empty self cache."""
    from repro.models.attention import attn_forward
    from repro.models.transformer import _attn_block_fwd, AUX0
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
    (enc_x, _), _ = jax.lax.scan(enc_f, (enc_x, AUX0), params["encoder"])
    enc_out = rmsnorm(enc_x, params["enc_norm"], cfg.norm_eps)

    Hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim

    def xkv_f(_, p_l):
        k = jnp.einsum("bsd,dhk->bshk", enc_out,
                       p_l["xattn"]["w_k"].astype(dtype))
        v = jnp.einsum("bsd,dhk->bshk", enc_out,
                       p_l["xattn"]["w_v"].astype(dtype))
        return None, {"k": k, "v": v}
    _, cross = jax.lax.scan(xkv_f, None, params["decoder"])

    mdt = jnp.dtype(cfg.dtype)
    self_c = {"k": jnp.zeros((cfg.num_layers, B, max_decode_len, Hkv, dh),
                             mdt),
              "v": jnp.zeros((cfg.num_layers, B, max_decode_len, Hkv, dh),
                             mdt)}
    return enc_out, {"decoder": {"self": self_c, "cross": cross}}


def decode_step(params, cfg: ModelConfig, mctx: MCtx, cache: dict,
                tokens: jax.Array, pos) -> tuple[jax.Array, dict]:
    """One token step. tokens: (B, 1) int32; pos: scalar position."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tokens, dtype)
        x = mctx.constrain(x, ("act_batch", None, "act_embed"))
    new_cache: dict[str, Any] = {}

    if cfg.encoder_decoder:
        x = x + sinusoidal_pos_emb(jnp.full((1,), pos),
                                   cfg.d_model).astype(dtype)

        def f(x, args):
            p_l, c_l = args
            h = rmsnorm(x, p_l["ln1"], cfg.norm_eps)
            a, kv = attn_decode(p_l["attn"], h, pos,
                                mctx.constrain_kv(c_l["self"]), cfg,
                                use_rope=False)
            x = x + a
            hx = rmsnorm(x, p_l["ln_x"], cfg.norm_eps)
            x = x + attn_decode_cross(p_l["xattn"], hx, c_l["cross"], cfg)
            f_ = mlp_apply(p_l["mlp"],
                           rmsnorm(x, p_l["ln2"], cfg.norm_eps), gated=False)
            return x + f_, kv
        dec = cache["decoder"]
        x, rows = jax.lax.scan(f, x, (params["decoder"], dec))
        new_cache["decoder"] = {"self": write_rows(dec["self"], rows, pos),
                                "cross": dec["cross"]}
    else:
        shared = params.get("shared_attn")
        for seg in segment_plan(cfg):
            x, c = seg_decode(params[seg.name], cache[seg.name], x, pos,
                              cfg, mctx, seg, shared_attn=shared)
            new_cache[seg.name] = c
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("logits"):
        logits = unembed(params["embed"], x, cfg.tie_embeddings)
        logits = mctx.constrain(logits, ("act_batch", None, "act_vocab"))
    return logits, new_cache
