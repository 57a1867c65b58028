"""Attention: GQA (full / sliding-window / local:global), MLA, decode paths.

Prefill/train use *chunked* attention — a lax.scan over query blocks so the
(S x S) score matrix is never materialized (O(q_chunk x S_kv) transient, the
XLA-path equivalent of the Pallas flash kernel in repro.kernels). Sliding
windows additionally slice the KV to (window + q_chunk), making SWA cost
O(S * window).

Decode uses single-token attention against a KV cache; for seq-sharded
caches (long_500k) XLA partitions the reductions (flash-decoding style).
``attn_decode`` reads the cache and never writes it: it returns the new
token's K/V row, and ``models.decode`` writes the rows in place after the
layers.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models.layers import apply_rope, rmsnorm, rmsnorm_spec
from repro.models.params import ParamSpec

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Core chunked attention
# --------------------------------------------------------------------------


def _gqa_scores(q, k):
    """q: (B, Sq, Hkv, G, dh), k: (B, Sk, Hkv, dh) -> (B, Hkv, G, Sq, Sk)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_ctx(p, v):
    """p: (B, Hkv, G, Sq, Sk), v: (B, Sk, Hkv, dh) -> (B, Sq, Hkv, G, dh)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(p.dtype))


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      q_chunk: int = 512, q_offset: int = 0,
                      scale: Optional[float] = None) -> jax.Array:
    """q: (B, Sq, Hq, dh); k, v: (B, Skv, Hkv, dh) -> (B, Sq, Hq, dh).

    ``q_offset`` is the absolute position of q[0] relative to k[0]
    (chunked-prefill support). ``window`` > 0 restricts each query to the
    last ``window`` keys (inclusive of self).
    """
    B, Sq, Hq, dh = q.shape
    _, Skv, Hkv, _ = k.shape
    dv = v.shape[-1]                 # may differ from dh (MLA)
    G = Hq // Hkv
    scale = scale if scale is not None else dh ** -0.5
    qc = q_chunk if (Sq % q_chunk == 0 and Sq >= q_chunk) else Sq
    nq = Sq // qc
    qg = q.reshape(B, nq, qc, Hkv, G, dh)

    use_window = window > 0 and Skv > window + qc
    kv_span = window + qc if use_window else Skv

    def one_chunk(q_c, c_idx):
        # q_c: (B, qc, Hkv, G, dh)
        q0 = c_idx * qc + q_offset                   # abs pos of first query
        if use_window:
            start = jnp.clip(q0 - window, 0, Skv - kv_span)
            k_c = jax.lax.dynamic_slice_in_dim(k, start, kv_span, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v, start, kv_span, axis=1)
            kv_pos = start + jnp.arange(kv_span)
        else:
            k_c, v_c = k, v
            kv_pos = jnp.arange(Skv)
        scores = _gqa_scores(q_c, k_c) * scale       # (B,Hkv,G,qc,kv)
        q_pos = q0 + jnp.arange(qc)
        mask = jnp.ones((qc, kv_span), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(mask, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        ctx = _gqa_ctx(p, v_c)                       # (B,qc,Hkv,G,dh)
        return ctx.astype(q.dtype)

    if nq == 1:
        out = one_chunk(qg[:, 0], jnp.int32(0))
        return out.reshape(B, Sq, Hq, dv)

    def body(_, args):
        q_c, idx = args
        return None, one_chunk(q_c, idx)

    _, outs = jax.lax.scan(body, None,
                           (qg.swapaxes(0, 1), jnp.arange(nq)))
    return outs.swapaxes(0, 1).reshape(B, Sq, Hq, dv)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid_mask: jax.Array,
                     scale: Optional[float] = None) -> jax.Array:
    """q: (B, 1, Hq, dh); caches: (B, S, Hkv, dh); valid_mask: (S,) or (B,S)."""
    B, _, Hq, dh = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = scale if scale is not None else dh ** -0.5
    qg = q.reshape(B, 1, Hkv, G, dh)
    scores = _gqa_scores(qg, k_cache) * scale        # (B,Hkv,G,1,S)
    if valid_mask.ndim == 1:
        valid_mask = valid_mask[None, :]
    scores = jnp.where(valid_mask[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    ctx = _gqa_ctx(p, v_cache)
    return ctx.reshape(B, 1, Hq, dh).astype(q.dtype)


# --------------------------------------------------------------------------
# Standard (GQA) attention block projections
# --------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, Hq, Hkv, dh = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.resolved_head_dim)
    specs = {
        "w_q": ParamSpec((d, Hq, dh), ("embed", "heads", None)),
        "w_k": ParamSpec((d, Hkv, dh), ("embed", "kv_heads", None)),
        "w_v": ParamSpec((d, Hkv, dh), ("embed", "kv_heads", None)),
        "w_o": ParamSpec((Hq, dh, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        specs["b_q"] = ParamSpec((Hq, dh), ("heads", None), init="zeros")
        specs["b_k"] = ParamSpec((Hkv, dh), ("kv_heads", None), init="zeros")
        specs["b_v"] = ParamSpec((Hkv, dh), ("kv_heads", None), init="zeros")
    return specs


def _project_qkv(p: dict, x_q, x_kv, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x_q, p["w_q"].astype(x_q.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x_kv, p["w_k"].astype(x_kv.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x_kv, p["w_v"].astype(x_kv.dtype))
    if "b_q" in p:
        q = q + p["b_q"].astype(q.dtype)
        k = k + p["b_k"].astype(k.dtype)
        v = v + p["b_v"].astype(v.dtype)
    return q, k, v


def attn_forward(p: dict, x: jax.Array, positions: jax.Array,
                 cfg: ModelConfig, *, causal: bool = True,
                 window: int = 0, use_rope: bool = True,
                 x_kv: Optional[jax.Array] = None,
                 kv_positions: Optional[jax.Array] = None,
                 q_chunk: int = 512, mctx=None) -> tuple[jax.Array, dict]:
    """Full-sequence attention (train / prefill). Returns (out, kv) where kv
    holds the rope'd k/v for cache construction."""
    x_kv = x if x_kv is None else x_kv
    with jax.named_scope("qkv"):
        q, k, v = _project_qkv(p, x, x_kv, cfg)
        if mctx is not None:
            # pin heads to 'model' (TP) — see mlp_apply (§Perf A3)
            hax = ("act_batch", None, "act_heads", None)
            q = mctx.constrain(q, hax)
            k = mctx.constrain(k, hax)
            v = mctx.constrain(v, hax)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope)
            kp = positions if kv_positions is None else kv_positions
            k = apply_rope(k, kp, cfg.rope_theta, cfg.mrope)
    with jax.named_scope("attend"):
        if (mctx is not None
                and mctx.parallel.attention_kernel == "pallas"
                and q.shape[1] == k.shape[1]):
            # TPU hot-spot path: the Pallas flash kernel (repro.kernels).
            # Semantics == chunked_attention (tests/test_kernels.py).
            ctx = _flash_attention(q, k, v, mctx, causal=causal,
                                   window=window)
        else:
            ctx = chunked_attention(q, k, v, causal=causal, window=window,
                                    q_chunk=q_chunk)
    with jax.named_scope("out"):
        out = jnp.einsum("bshk,hkd->bsd", ctx, p["w_o"].astype(ctx.dtype))
    return out, {"k": k, "v": v}


def _flash_attention(q, k, v, mctx, *, causal: bool, window: int):
    """The flash kernel over (B, S, H, dh) q/k/v. Mosaic kernels are not
    partitioned automatically, so on a mesh of several devices each one
    runs the kernel on its own batch rows and heads under shard_map."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.sharding import spec_for

    def call(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_blk=min(512, q.shape[2]),
                               kv_blk=min(512, k.shape[2]))

    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    mesh = mctx.mesh
    if mesh.size == 1:
        return call(qt, kt, vt).transpose(0, 2, 1, 3)
    axes = ("act_batch", "act_heads", None, None)
    q_spec = spec_for(axes, mctx.rules, qt.shape, mesh)
    kv_spec = spec_for(axes, mctx.rules, kt.shape, mesh)
    if tuple(q_spec)[1:2] != tuple(kv_spec)[1:2]:
        raise ValueError(
            f"flash attention on mesh {dict(mesh.shape)}: {qt.shape[1]} "
            f"query heads and {kt.shape[1]} kv heads split differently "
            f"({q_spec} vs {kv_spec})")
    out = jax.shard_map(call, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                        out_specs=q_spec, check_vma=False)(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def decode_slot(pos, S: int, window: int = 0):
    """The cache index a decode step at ``pos`` writes: ``pos % S`` in a
    ring (window > 0), else ``pos``."""
    return pos % S if window > 0 else pos


def decode_attention_with_row(q: jax.Array, k_cache: jax.Array,
                              v_cache: jax.Array, cached: jax.Array,
                              k_new: jax.Array, v_new: jax.Array
                              ) -> jax.Array:
    """``decode_attention`` over an unmodified cache plus the new token's row.

    q: (B, 1, Hq, dh); caches: (B, S, Hkv, dh); cached: (S,) the slots that
    hold earlier tokens; k_new, v_new: (B, 1, Hkv, dh). The new row is one
    more score column of the same float32 softmax, so this equals
    ``decode_attention`` over the cache with the row written in. The
    column is not concatenated to the cache's scores, so a sequence-sharded
    cache keeps its sharding through the reductions.
    """
    B, _, Hq, dh = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    scale = dh ** -0.5
    qg = q.reshape(B, 1, Hkv, G, dh)
    scores = _gqa_scores(qg, k_cache) * scale        # (B,Hkv,G,1,S)
    scores = jnp.where(cached[None, None, None, None, :], scores, NEG_INF)
    s_new = _gqa_scores(qg, k_new) * scale           # (B,Hkv,G,1,1)
    m = jnp.maximum(scores.max(-1, keepdims=True), s_new)
    e, e_new = jnp.exp(scores - m), jnp.exp(s_new - m)
    total = e.sum(-1, keepdims=True) + e_new
    ctx = _gqa_ctx(e / total, v_cache) + _gqa_ctx(e_new / total, v_new)
    return ctx.reshape(B, 1, Hq, dh).astype(q.dtype)


def attn_decode(p: dict, x: jax.Array, pos, cache: dict,
                cfg: ModelConfig, *, window: int = 0,
                use_rope: bool = True) -> tuple[jax.Array, dict]:
    """One decode step. x: (B, 1, d). cache: {k,v: (B, S_or_W, Hkv, dh)}.

    ``pos`` is the current absolute position (scalar int); a ring cache
    (window > 0 and cache length == window) holds it at pos % window.
    The cache is only read here: the step attends over the earlier tokens'
    slots plus its own K/V, and returns (out, {k, v: (B, 1, Hkv, dh)}), the
    new rows, which the caller writes at ``decode_slot`` after the layers.
    """
    with jax.named_scope("qkv"):
        q, k_new, v_new = _project_qkv(p, x, x, cfg)
        B = x.shape[0]
        positions = jnp.full((B, 1), pos)
        if cfg.mrope:
            positions = jnp.broadcast_to(positions, (3, B, 1))
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope)
            k_new = apply_rope(k_new, positions, cfg.rope_theta, cfg.mrope)
    S = cache["k"].shape[1]
    rows = {"k": k_new.astype(cache["k"].dtype),
            "v": v_new.astype(cache["v"].dtype)}
    slots = jnp.arange(S)
    cached = (slots < pos) & (slots != decode_slot(pos, S, window))
    with jax.named_scope("attend"):
        ctx = decode_attention_with_row(
            q, cache["k"].astype(q.dtype), cache["v"].astype(q.dtype),
            cached, rows["k"].astype(q.dtype), rows["v"].astype(q.dtype))
    with jax.named_scope("out"):
        out = jnp.einsum("bshk,hkd->bsd", ctx, p["w_o"].astype(ctx.dtype))
    return out, rows


def attn_decode_cross(p: dict, x: jax.Array, cross_kv: dict,
                      cfg: ModelConfig) -> jax.Array:
    """Cross-attention decode step against precomputed encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(x.dtype))
    if "b_q" in p:
        q = q + p["b_q"].astype(q.dtype)
    S = cross_kv["k"].shape[1]
    valid = jnp.ones((S,), bool)
    ctx = decode_attention(q, cross_kv["k"].astype(q.dtype),
                           cross_kv["v"].astype(q.dtype), valid)
    return jnp.einsum("bshk,hkd->bsd", ctx, p["w_o"].astype(ctx.dtype))


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# --------------------------------------------------------------------------


def mla_specs(cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": ParamSpec((d, m.q_lora_rank), ("embed", None)),
        "q_norm": rmsnorm_spec(m.q_lora_rank),
        "w_uq": ParamSpec((m.q_lora_rank, H, qk), (None, "heads", None)),
        "w_dkv": ParamSpec((d, m.kv_lora_rank), ("embed", None)),
        "kv_norm": rmsnorm_spec(m.kv_lora_rank),
        "w_kr": ParamSpec((d, m.qk_rope_head_dim), ("embed", None)),
        "w_uk": ParamSpec((m.kv_lora_rank, H, m.qk_nope_head_dim),
                          (None, "heads", None)),
        "w_uv": ParamSpec((m.kv_lora_rank, H, m.v_head_dim),
                          (None, "heads", None)),
        "w_o": ParamSpec((H, m.v_head_dim, d), ("heads", None, "embed")),
    }


def _mla_q(p, x, positions, cfg):
    m = cfg.mla
    cq = rmsnorm(x @ p["w_dq"].astype(x.dtype), p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"].astype(x.dtype))
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions,
                        cfg.rope_theta)
    return q_nope, q_rope


def _mla_latents(p, x, positions, cfg):
    ckv = rmsnorm(x @ p["w_dkv"].astype(x.dtype), p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope((x @ p["w_kr"].astype(x.dtype))[:, :, None, :],
                        positions, cfg.rope_theta)[:, :, 0, :]
    return ckv, k_rope


def mla_forward(p: dict, x: jax.Array, positions: jax.Array,
                cfg: ModelConfig, q_chunk: int = 512):
    """Train/prefill MLA. Returns (out, latent_cache)."""
    m = cfg.mla
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(p, x, positions, cfg)
    ckv, k_rope = _mla_latents(p, x, positions, cfg)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhv->bshv", ckv, p["w_uv"].astype(x.dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], m.qk_rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    ctx = chunked_attention(q, k, v, causal=True, q_chunk=q_chunk,
                            scale=scale)
    out = jnp.einsum("bshv,hvd->bsd", ctx, p["w_o"].astype(ctx.dtype))
    return out, {"ckv": ckv, "k_rope": k_rope}


def mla_decode(p: dict, x: jax.Array, pos, cache: dict, cfg: ModelConfig):
    """Absorbed-form MLA decode: scores/ctx computed in latent space —
    per-step cost O(S * (kv_lora + rope)) per head, the DeepSeek serving
    formulation. cache: {ckv: (B, S, r), k_rope: (B, S, rope)}."""
    m = cfg.mla
    B = x.shape[0]
    positions = jnp.full((B, 1), pos)
    q_nope, q_rope = _mla_q(p, x, positions, cfg)          # (B,1,H,*)
    ckv_new, krope_new = _mla_latents(p, x, positions, cfg)
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], krope_new.astype(cache["k_rope"].dtype), pos, axis=1)
    S = ckv.shape[1]
    # Absorb W_uk into q: (B,1,H,nope) x (r,H,nope) -> (B,1,H,r)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(x.dtype))
    scores = (jnp.einsum("bshr,bkr->bhsk", q_abs.astype(jnp.float32),
                         ckv.astype(jnp.float32)) +
              jnp.einsum("bshr,bkr->bhsk", q_rope.astype(jnp.float32),
                         k_rope.astype(jnp.float32)))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = scores.astype(jnp.float32) * scale
    valid = jnp.arange(S) <= pos
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    pr = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bhsk,bkr->bshr", pr, ckv.astype(jnp.float32))
    out_h = jnp.einsum("bshr,rhv->bshv", ctx_lat.astype(x.dtype),
                       p["w_uv"].astype(x.dtype))
    out = jnp.einsum("bshv,hvd->bsd", out_h, p["w_o"].astype(x.dtype))
    return out, {"ckv": ckv, "k_rope": k_rope}
