"""Pallas TPU paged decode attention (vLLM-style block-table indirection).

The block table rides in scalar-prefetch memory (SMEM) so each grid step's
``index_map`` dereferences it to pick WHICH KV page to DMA into VMEM — the
kernel-level analogue of the paper's pointer-chasing microbenchmark, and the
mechanism that makes tier-interleaved KV pages (repro.core.placement)
addressable: the table maps logical pages to wherever the pager put them.

Grid: (B * Hkv, pages_per_seq); the page axis is sequential with flash
accumulators in VMEM scratch. One query token per sequence (decode).

``paged_attention_quant`` is the fused int8 variant: K/V pools arrive as
int8 plus per-(page, kv_head) fp32 scales (kernels/quant.quantize_pages
layout), the page DMA moves half the bytes over the contended HBM<->host
path, and dequantization happens in-register after the VMEM load — no fp
copy of the pool ever materializes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# Scale planes of the int8 kernel travel in (8, 128) f32 blocks, the
# smallest tile the TPU compiler accepts: each block holds the scales of
# SCALE_BLOCK consecutive (page, kv_head) rows, row-major.
SCALE_ROWS = 8
SCALE_BLOCK = SCALE_ROWS * LANES


def _flash_page_step(seq_lens, q, k, v, o_ref, m_ref, l_ref, acc_ref, *,
                     page: int, n_pages_per_seq: int, scale: float, G: int,
                     hkv: int):
    """One flash-accumulator update over a single (already fp32) KV page.

    Shared by the fp and int8 kernels — the only difference between them is
    how k/v were produced from their VMEM blocks.
    """
    bh = pl.program_id(0)
    j = pl.program_id(1)
    b = bh // hkv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (G, page), 1)
    valid = pos < seq_lens[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # Mask p explicitly: when every position so far is invalid (a
    # zero-length sequence whose block-table row is pure padding), m_new
    # stays at NEG_INF and exp(s - m_new) would otherwise be exp(0)=1 —
    # attending to whatever live page the padding aliases.
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == n_pages_per_seq - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _kernel(block_table, seq_lens,            # scalar-prefetch (SMEM)
            q_ref, k_ref, v_ref, o_ref,       # blocks (VMEM)
            m_ref, l_ref, acc_ref, *,
            page: int, n_pages_per_seq: int, scale: float, G: int,
            hkv: int):
    q = q_ref[0].astype(jnp.float32)                 # (G, d)
    k = k_ref[0].astype(jnp.float32)                 # (page, d)
    v = v_ref[0].astype(jnp.float32)
    _flash_page_step(seq_lens, q, k, v, o_ref, m_ref, l_ref, acc_ref,
                     page=page, n_pages_per_seq=n_pages_per_seq,
                     scale=scale, G=G, hkv=hkv)


def _pick_scale(s_ref, r):
    """The scale at flat position ``r`` of an (8, 128) scale block, as a
    (1, 1) array (masked reduce: no dynamic sublane/lane indexing)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    sel = jnp.where(rows * LANES + lanes == r, s_ref[...], 0.0)
    return jnp.sum(jnp.sum(sel, axis=1, keepdims=True), axis=0,
                   keepdims=True)


def _kernel_quant(block_table, seq_lens,      # scalar-prefetch (SMEM)
                  q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  page: int, n_pages_per_seq: int, scale: float, G: int,
                  hkv: int):
    """int8 page blocks + per-(page, head) scale blocks: dequantize in
    registers right after the VMEM DMA — the DMA itself moved int8."""
    bh = pl.program_id(0)
    row = block_table[bh // hkv, pl.program_id(1)] * hkv + bh % hkv
    r = row % SCALE_BLOCK
    q = q_ref[0].astype(jnp.float32)                 # (G, d)
    k = k_ref[0].astype(jnp.float32) * _pick_scale(ks_ref, r)
    v = v_ref[0].astype(jnp.float32) * _pick_scale(vs_ref, r)
    _flash_page_step(seq_lens, q, k, v, o_ref, m_ref, l_ref, acc_ref,
                     page=page, n_pages_per_seq=n_pages_per_seq,
                     scale=scale, G=G, hkv=hkv)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_table: jax.Array, seq_lens: jax.Array, *,
                    scale: float | None = None,
                    interpret: bool = True) -> jax.Array:
    """q: (B, Hq, d); pages: (n_pages, page, Hkv, d);
    block_table: (B, pages_per_seq); seq_lens: (B,) -> (B, Hq, d)."""
    B, Hq, d = q.shape
    n_pages, page, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    pps = block_table.shape[1]
    scale = d ** -0.5 if scale is None else scale

    # layouts: q -> (B*Hkv, G, d); pages -> (n_pages, Hkv, page, d)
    qf = q.reshape(B, Hkv, G, d).reshape(B * Hkv, G, d)
    kf = k_pages.transpose(0, 2, 1, 3).reshape(n_pages * Hkv, page, d)
    vf = v_pages.transpose(0, 2, 1, 3).reshape(n_pages * Hkv, page, d)

    def page_map(bh, j, table, lens):
        b = bh // Hkv
        h = bh % Hkv
        return (table[b, j] * Hkv + h, 0, 0)

    kernel = functools.partial(_kernel, page=page, n_pages_per_seq=pps,
                               scale=scale, G=G, hkv=Hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hkv, pps),
        in_specs=[
            pl.BlockSpec((1, G, d), lambda bh, j, *_: (bh, 0, 0)),
            pl.BlockSpec((1, page, d), page_map),
            pl.BlockSpec((1, page, d), page_map),
        ],
        out_specs=pl.BlockSpec((1, G, d), lambda bh, j, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, d), q.dtype),
        interpret=interpret,
    )(block_table, seq_lens, qf, kf, vf)
    return out.reshape(B, Hkv, G, d).reshape(B, Hq, d)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_quant(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, k_scales: jax.Array,
                          v_scales: jax.Array, block_table: jax.Array,
                          seq_lens: jax.Array, *,
                          scale: float | None = None,
                          interpret: bool = True) -> jax.Array:
    """Fused int8 paged decode attention.

    q: (B, Hq, d) fp; k/v_pages: (n_pages, page, Hkv, d) int8;
    k/v_scales: (n_pages, Hkv) f32 (kernels/quant.quantize_pages layout);
    block_table: (B, pages_per_seq); seq_lens: (B,) -> (B, Hq, d).

    Identical grid/indirection to ``paged_attention``; each page DMA moves
    int8 (≈2x fewer bytes than bf16) plus one scalar scale per (page, head),
    and the dequant multiply runs on the VPU before the MXU dot.
    """
    B, Hq, d = q.shape
    n_pages, page, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    pps = block_table.shape[1]
    scale = d ** -0.5 if scale is None else scale

    qf = q.reshape(B, Hkv, G, d).reshape(B * Hkv, G, d)
    kf = k_pages.transpose(0, 2, 1, 3).reshape(n_pages * Hkv, page, d)
    vf = v_pages.transpose(0, 2, 1, 3).reshape(n_pages * Hkv, page, d)
    # scale planes: the n_pages*Hkv scalars row-major in (8, 128) blocks;
    # each grid step DMAs the block holding its page's scale
    n_rows = n_pages * Hkv
    n_blk = -(-n_rows // SCALE_BLOCK)

    def plane(s):
        flat = jnp.pad(s.reshape(n_rows).astype(jnp.float32),
                       (0, n_blk * SCALE_BLOCK - n_rows))
        return flat.reshape(n_blk * SCALE_ROWS, LANES)
    ksf, vsf = plane(k_scales), plane(v_scales)

    def page_map(bh, j, table, lens):
        b = bh // Hkv
        h = bh % Hkv
        return (table[b, j] * Hkv + h, 0, 0)

    def scale_map(bh, j, table, lens):
        b = bh // Hkv
        h = bh % Hkv
        return ((table[b, j] * Hkv + h) // SCALE_BLOCK, 0)

    kernel = functools.partial(_kernel_quant, page=page,
                               n_pages_per_seq=pps, scale=scale, G=G,
                               hkv=Hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hkv, pps),
        in_specs=[
            pl.BlockSpec((1, G, d), lambda bh, j, *_: (bh, 0, 0)),
            pl.BlockSpec((1, page, d), page_map),
            pl.BlockSpec((1, page, d), page_map),
            pl.BlockSpec((SCALE_ROWS, LANES), scale_map),
            pl.BlockSpec((SCALE_ROWS, LANES), scale_map),
        ],
        out_specs=pl.BlockSpec((1, G, d), lambda bh, j, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, d), q.dtype),
        interpret=interpret,
    )(block_table, seq_lens, qf, kf, vf, ksf, vsf)
    return out.reshape(B, Hkv, G, d).reshape(B, Hq, d)
