"""Paged KV-cache manager with tier-interleaved page placement.

vLLM-style paging married to the paper's §3.4 weighted interleaving: the
page pool is split across memory tiers by `repro.core.placement.
interleave_pages` weights (cost-model optimal by default), the block table
maps logical pages to pool slots, and `repro.kernels.paged_attention`
dereferences the table inside the kernel (scalar-prefetch indirection — the
kernel-level pointer chase).

Pool layout: one pool array per tier, `(n_pages, page_size, Hkv, dh)`.
HBM-tier pages are attended directly; host-tier pages are fetched on demand
(sync, paper-faithful) or prefetched a step ahead (beyond-paper overlap).

Quantized cold tier (``PagerConfig(kv_dtype="int8")``): host-tier pages are
stored as int8 with per-(page, kv_head) fp32 scales (kernels/quant
``quantize_pages`` layout), so every byte crossing the contended host<->HBM
link is compressed ~2x — the single highest-leverage optimization when the
coherent link, not compute, bounds decode (the paper's through-line).
``attend_quant`` runs the fused int8 paged-attention kernel directly over
quantized pools (in-register dequant, no fp copy materialized).

DMA QoS (``PagerConfig.prefetch_priority``/``prefetch_weight``): page
fetches are deadline-critical, so ``plan_prefetch`` issues them in a
high-priority fabric class by default — on a shared PCIe/CXL link they ride
over bulk best-effort streams (weight offload) instead of splitting the
link 50/50 with them (``repro.fabric.contention`` strict-priority sharing).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.placement import interleave_pages
from repro.heimdall.harness import place
from repro.obs.trace import NULL_TRACER


@dataclasses.dataclass
class PagerConfig:
    page_size: int = 64
    n_pages: int = 256
    kv_heads: int = 2
    head_dim: int = 32
    weights: tuple = (1, 0)          # (hbm, host) interleave weights
    dtype: str = "bfloat16"
    kv_dtype: Optional[str] = None   # "int8" -> quantized host tier
    # DMA QoS class of page fetches (fabric.contention.Flow semantics):
    # deadline-critical page DMAs ride the high-priority queue over bulk
    # best-effort streams (weight offload) by default.
    prefetch_priority: int = 1
    prefetch_weight: float = 1.0

    def __post_init__(self):
        if self.kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8', "
                             f"got {self.kv_dtype!r}")
        if self.prefetch_weight <= 0:
            raise ValueError(f"prefetch_weight must be > 0, "
                             f"got {self.prefetch_weight}")


class PagedKVCache:
    """Per-layer paged KV store with tiered page pools."""

    TIERS = ("hbm", "host")

    def __init__(self, cfg: PagerConfig, tracer=NULL_TRACER):
        self.cfg = cfg
        # Observability (repro.obs): spill/fetch/append spans plus
        # hit/miss/bytes-moved counters per tier; NULL_TRACER by default so
        # the decode hot path pays nothing when tracing is off.
        self.tracer = tracer
        shape = (cfg.n_pages, cfg.page_size, cfg.kv_heads, cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        self.tier_of_page = interleave_pages(cfg.n_pages, list(cfg.weights))
        self.k_pool = place(jnp.zeros(shape, dt), "hbm")
        self.v_pool = place(jnp.zeros(shape, dt), "hbm")
        # host-tier pages: their backing moves to a compact host shadow,
        # one row per host-tier page in _host_idx order, replaced whole by
        # every spill (None until the first spill, released by the fetch)
        self._host_mask = self.tier_of_page == 1
        self._host_idx = np.nonzero(self._host_mask)[0]
        self._drop_shadow()
        self.free = collections.deque(range(cfg.n_pages))
        self.tables: dict[int, list[int]] = {}    # seq id -> page ids
        self.lens: dict[int, int] = {}
        # block_table/seq_lens cache, keyed by the seq-id tuple; one decode
        # step calls attend once per layer, so rebuilding the padded numpy
        # table per call is pure overhead — invalidated on any table change
        self._bt_cache: dict[tuple, tuple] = {}
        # quantized-pool cache for attend_quant, invalidated on pool writes
        self._quant_pools = None

    # -- allocation --------------------------------------------------------
    def allocate(self, seq_id: int) -> None:
        self.tables[seq_id] = []
        self.lens[seq_id] = 0
        self._bt_cache.clear()

    def free_seq(self, seq_id: int) -> None:
        self.free.extend(self.tables.pop(seq_id, []))
        self.lens.pop(seq_id, None)
        self._bt_cache.clear()

    def _grow(self, seq_id: int, new_len: int) -> None:
        need = -(-new_len // self.cfg.page_size)
        table = self.tables[seq_id]
        while len(table) < need:
            if not self.free:
                raise MemoryError("page pool exhausted")
            table.append(self.free.popleft())

    # -- writes -------------------------------------------------------------
    def append(self, seq_id: int, k: jax.Array, v: jax.Array) -> None:
        """Append T tokens of K/V: arrays (T, Hkv, dh).

        One batched scatter per pool (all T (page, offset) destinations at
        once) instead of a per-token ``.at[].set`` chain — T dispatches and
        T pool copies collapse into one.
        """
        T = k.shape[0]
        start = self.lens[seq_id]
        with self.tracer.span("pager.append", track=("pager", "writes"),
                              cat="pager", seq=seq_id, tokens=T):
            self._grow(seq_id, start + T)
            ps = self.cfg.page_size
            pos = np.arange(start, start + T)
            table = np.asarray(self.tables[seq_id], np.int32)
            pages = jnp.asarray(table[pos // ps])
            offs = jnp.asarray(pos % ps, jnp.int32)
            self.k_pool = self.k_pool.at[pages, offs].set(
                k.astype(self.k_pool.dtype))
            self.v_pool = self.v_pool.at[pages, offs].set(
                v.astype(self.v_pool.dtype))
        self.lens[seq_id] = start + T
        self._bt_cache.clear()
        self._quant_pools = None
        # the HBM pool is the live copy again; any host shadow is stale —
        # a fetch_spilled without a fresh spill must not clobber this write
        self._drop_shadow()
        if self.tracer.enabled:
            elem = jnp.dtype(self.cfg.dtype).itemsize
            self.tracer.metrics.add("pager.append.tokens", T)
            self.tracer.metrics.add(
                "pager.bytes_written", tier="hbm",
                value=2 * T * self.cfg.kv_heads * self.cfg.head_dim * elem)

    # -- reads ---------------------------------------------------------------
    def block_table(self, seq_ids: list[int]) -> tuple:
        """Padded (B, max_pages) block table + (B,) seq lens (cached until
        the next append/allocate/free_seq)."""
        key = tuple(seq_ids)
        hit = self._bt_cache.get(key)
        if hit is not None:
            return hit
        # at least one page column so an all-fresh batch still yields a
        # valid (B, 1) table; padded entries are masked by seq_lens==0
        mx = max(1, max(len(self.tables[s]) for s in seq_ids))
        bt = np.zeros((len(seq_ids), mx), np.int32)
        for i, s in enumerate(seq_ids):
            pages = self.tables[s]
            bt[i, :len(pages)] = pages
            if len(pages) < mx:                  # pad with a valid page id
                bt[i, len(pages):] = pages[-1] if pages else 0
        lens = np.array([self.lens[s] for s in seq_ids], np.int32)
        out = (jnp.asarray(bt), jnp.asarray(lens))
        self._bt_cache[key] = out
        return out

    def _count_page_touches(self, seq_ids: list[int]) -> None:
        """Tier hit/miss counters for one attention call: an HBM-resident
        page is a hit (attended in place), a host-tier page is a miss (it
        must cross the contended link before the step can see it)."""
        hits = misses = 0
        for s in seq_ids:
            for p in self.tables[s]:
                if self.tier_of_page[p] == 1:
                    misses += 1
                else:
                    hits += 1
        self.tracer.metrics.add("pager.page_hits", hits, tier="hbm")
        self.tracer.metrics.add("pager.page_misses", misses, tier="host")

    def attend(self, q: jax.Array, seq_ids: list[int],
               interpret: Optional[bool] = None) -> jax.Array:
        """Decode attention via the Pallas paged kernel. q: (B, Hq, dh)."""
        from repro.kernels.paged_attention import paged_attention
        if self.tracer.enabled:
            self._count_page_touches(seq_ids)
        bt, lens = self.block_table(seq_ids)
        return paged_attention(q, self.k_pool, self.v_pool, bt, lens,
                               interpret=interpret)

    def attend_quant(self, q: jax.Array, seq_ids: list[int],
                     interpret: Optional[bool] = None) -> jax.Array:
        """Decode attention over int8 pools via the fused quant kernel.

        Quantizes the live pool per (page, kv_head) and attends without
        materializing an fp copy — the path a fully-compressed KV residency
        takes (pages that arrived int8 from the host tier stay int8). The
        quantized pools are cached until the next pool write, so a decode
        loop pays the quantization once per appended step, not per layer.
        """
        from repro.kernels.paged_attention import paged_attention_quant
        from repro.kernels.quant import quantize_pages
        if self.tracer.enabled:
            self._count_page_touches(seq_ids)
        bt, lens = self.block_table(seq_ids)
        if self._quant_pools is None:
            self._quant_pools = (quantize_pages(self.k_pool,
                                                interpret=interpret),
                                 quantize_pages(self.v_pool,
                                                interpret=interpret))
        (kq, ks), (vq, vs) = self._quant_pools
        return paged_attention_quant(q, kq, vq, ks, vs, bt, lens,
                                     interpret=interpret)

    # -- tier maintenance -----------------------------------------------------
    def _drop_shadow(self) -> None:
        """Forget the host shadow: it is stale (or consumed), so a later
        fetch must not write it back over the live HBM pool."""
        self._spilled = False
        self.k_pool_host = self.v_pool_host = None
        self.k_scales_host = self.v_scales_host = None

    def spill_cold_pages(self) -> int:
        """Move host-tier-assigned pages' backing to host memory (the
        paper's cold-page demotion, TPP-style). With ``kv_dtype="int8"``
        the spilled pages are quantized on the way out, so the host link
        carries half the bytes. Returns pages spilled."""
        if not self._host_mask.any():
            return 0
        n_spilled = int(self._host_mask.sum())
        with self.tracer.span("pager.spill", track=("pager", "tiers"),
                              cat="pager", pages=n_spilled):
            # gather (and with int8, quantize) only the host-assigned rows
            # in device memory; the one device_put is the only op that
            # crosses into host memory — no op mixes the two spaces
            idx = jnp.asarray(self._host_idx)
            k_cold = jnp.take(self.k_pool, idx, axis=0)
            v_cold = jnp.take(self.v_pool, idx, axis=0)
            host = self.k_pool.sharding.with_memory_kind("pinned_host")
            if self.cfg.kv_dtype == "int8":
                from repro.kernels.quant import quantize_pages
                kq, ks = quantize_pages(k_cold)
                vq, vs = quantize_pages(v_cold)
                (self.k_pool_host, self.v_pool_host, self.k_scales_host,
                 self.v_scales_host) = jax.device_put((kq, vq, ks, vs), host)
            else:
                self.k_pool_host, self.v_pool_host = jax.device_put(
                    (k_cold, v_cold), host)
        self._spilled = True
        self.tracer.metrics.add("pager.spill.pages", n_spilled, tier="host")
        self.tracer.metrics.add("pager.spill.bytes",
                                n_spilled * self.host_page_bytes,
                                tier="host")
        return n_spilled

    def fetch_spilled(self) -> None:
        """Bring spilled pages back next to the HBM pool (sync fetch — the
        paper-faithful mode; overlap belongs to the serving loop). int8
        pages cross the link compressed and dequantize on the HBM side.

        No-op until ``spill_cold_pages`` has actually populated the host
        shadow: a spurious fetch must not overwrite live HBM pages with a
        stale shadow. The shadow is consumed by the fetch — it
        goes stale the moment the live pool is appended to, so a fresh
        spill is required before the next fetch.
        """
        if not self._spilled or not self._host_mask.any():
            return
        n_pages = int(self._host_mask.sum())
        with self.tracer.span("pager.fetch", track=("pager", "tiers"),
                              cat="pager", pages=n_pages):
            # the compact shadow crosses the link whole (one device_put),
            # then dequantizes and scatters back in device memory
            idx = jnp.asarray(self._host_idx)
            dev = self.k_pool.sharding
            if self.cfg.kv_dtype == "int8":
                from repro.kernels.quant import dequantize_pages
                kq, vq, ks, vs = jax.device_put(
                    (self.k_pool_host, self.v_pool_host,
                     self.k_scales_host, self.v_scales_host), dev)
                k_h = dequantize_pages(kq, ks, out_dtype=self.k_pool.dtype)
                v_h = dequantize_pages(vq, vs, out_dtype=self.v_pool.dtype)
            else:
                k_h, v_h = jax.device_put(
                    (self.k_pool_host, self.v_pool_host), dev)
            self.k_pool = self.k_pool.at[idx].set(k_h)
            self.v_pool = self.v_pool.at[idx].set(v_h)
        self._drop_shadow()
        self._quant_pools = None
        self.tracer.metrics.add("pager.fetch.pages", n_pages, tier="host")
        self.tracer.metrics.add("pager.fetch.bytes",
                                n_pages * self.host_page_bytes,
                                tier="host")

    def retier(self, weights) -> dict:
        """Re-interleave pages across tiers (the elastic replan's "act"
        step): apply a new ``interleave_pages`` assignment, migrating any
        spilled data back next to the HBM pool first so nothing is lost.

        The degradation loop (``repro.runtime.degrade``) calls this with
        ``elastic.replan_interleave``'s output when a spill tier degrades
        or disappears — pages leave the sick tier, and the bytes that
        cross the (degraded) link to do so are the migration cost the
        caller accounts for. Returns ``{"to_fast", "to_slow", "migrated",
        "weights"}``: ``to_fast``/``to_slow`` count pages whose tier
        assignment changed; ``migrated`` is True when spilled host data
        actually moved (a live-HBM pool relabels for free).
        """
        new_assign = interleave_pages(self.cfg.n_pages, list(weights))
        old = self.tier_of_page
        to_fast = int(((old == 1) & (new_assign == 0)).sum())
        to_slow = int(((old == 0) & (new_assign == 1)).sum())
        migrated = bool(self._spilled and to_fast)
        with self.tracer.span("pager.retier", track=("pager", "tiers"),
                              cat="pager", to_fast=to_fast,
                              to_slow=to_slow):
            if self._spilled:
                # restore the live HBM copy before relabeling: the host
                # shadow is only meaningful under the old assignment
                self.fetch_spilled()
            self.tier_of_page = new_assign
            self._host_mask = new_assign == 1
            self._host_idx = np.nonzero(self._host_mask)[0]
        self.cfg = dataclasses.replace(self.cfg, weights=tuple(weights))
        self._bt_cache.clear()
        self._quant_pools = None
        if self.tracer.enabled:
            m = self.tracer.metrics
            m.add("pager.retier.pages_to_fast", to_fast)
            m.add("pager.retier.pages_to_slow", to_slow)
            if migrated:
                m.add("pager.retier.migrated_bytes",
                      to_fast * self.host_page_bytes, tier="host")
        return {"to_fast": to_fast, "to_slow": to_slow,
                "migrated": migrated, "weights": tuple(weights)}

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self.free) / self.cfg.n_pages

    # -- prefetch scheduling (fabric sim) -------------------------------------
    def page_bytes_for(self, tier: str) -> int:
        """Bytes one page fetch moves from this tier (K and V planes).

        Tier- and dtype-aware: the hot tier holds fp pages; with
        ``kv_dtype="int8"`` the host tier holds int8 pages plus one f32
        scale per (page, kv_head) per plane.
        """
        c = self.cfg
        elems = c.page_size * c.kv_heads * c.head_dim
        if tier == "host" and c.kv_dtype == "int8":
            return 2 * (elems + c.kv_heads * 4)     # int8 payload + scales
        return 2 * elems * jnp.dtype(c.dtype).itemsize

    @property
    def page_bytes(self) -> int:
        """Bytes per uncompressed (hot-tier) page fetch."""
        return self.page_bytes_for("hbm")

    @property
    def host_page_bytes(self) -> int:
        """Bytes per page actually crossing the host link on fetch."""
        return self.page_bytes_for("host")

    def host_pages(self, seq_ids: list[int]) -> list[int]:
        """Host-tier-resident pages of these sequences, in attention order
        (the order the decode step will touch them)."""
        pages = []
        for s in seq_ids:
            pages.extend(p for p in self.tables[s]
                         if self.tier_of_page[p] == 1 and p not in pages)
        return pages

    def plan_prefetch(self, seq_ids: list[int], system=None,
                      background: tuple = (),
                      weight: Optional[float] = None,
                      priority: Optional[int] = None,
                      tracer=None) -> "PrefetchPlan":
        """Schedule host->HBM page prefetches through the fabric simulator.

        Pages are fetched one at a time over the host link (one DMA queue),
        each flow chained behind the previous, co-scheduled against any
        ``background`` fabric flows (e.g. a weight-offload stream on the
        same PCIe link). Returns per-page ETAs so the serving loop knows
        which pages will be resident by the time the step needs them.
        Quantized pages (kv_dtype="int8") move ~2x fewer bytes, so their
        ETAs land ~2x sooner on a bandwidth-bound link.

        Page fetches are issued in the pager's DMA QoS class
        (``PagerConfig.prefetch_priority``/``prefetch_weight``, overridable
        here): at the default priority 1 they ride over best-effort bulk
        streams instead of splitting the link with them, which is the
        class-aware arbitration CXL-Interference shows a shared link needs.
        """
        src_tier = None
        if system is not None and getattr(system, "kv_tiers", None):
            src_tier = system.kv_tiers[1]     # the machine's own spill tier
        # logical page size + kv_dtype wire compression — transport's
        # PageTransfer vocabulary (wire bytes == host_page_bytes as ever)
        return plan_prefetch(
            self.host_pages(seq_ids), self.page_bytes,
            system=system, background=background,
            weight=self.cfg.prefetch_weight if weight is None else weight,
            priority=(self.cfg.prefetch_priority if priority is None
                      else priority),
            src_tier=src_tier,
            compression=self.page_bytes / self.host_page_bytes,
            tracer=self.tracer if tracer is None else tracer)


@dataclasses.dataclass(frozen=True)
class PrefetchPlan:
    """Fabric-simulated prefetch schedule for a set of host-tier pages.

    A thin page-id-keyed view over ``repro.transport.TransferPlan`` (kept
    as the pager's stable vocabulary); the underlying plan — route,
    per-transfer wire bytes, deadline accounting — rides along as
    ``transfer_plan`` when one was built.
    """
    order: tuple                 # page ids in fetch order
    eta: dict                    # page id -> estimated arrival time (s)
    total_time: float            # when the last page lands (s)
    effective_bw: float          # contended link bandwidth used (bytes/s)
    transfer_plan: Optional[object] = None   # transport.TransferPlan

    def ready_by(self, deadline: float) -> list[int]:
        """Pages resident if the decode step fires at `deadline`."""
        return [p for p in self.order if self.eta[p] <= deadline]


def plan_prefetch(pages: list, page_bytes: int, system=None,
                  background: tuple = (), weight: float = 1.0,
                  priority: int = 0, src_tier: Optional[str] = None,
                  tracer=NULL_TRACER, compression: float = 1.0,
                  background_nbytes: Optional[int] = None) -> PrefetchPlan:
    """Build a PrefetchPlan via ``repro.transport.plan_transfers`` (one
    chained-DMA simulation on the fabric — the single planner every
    byte-moving layer shares).

    ``system`` defaults to the TPU v5e preset (host_dram -> chip0 over
    PCIe). ``src_tier`` names the spill tier pages are fetched from
    (default ``"host"``; ``PagedKVCache.plan_prefetch`` passes the
    system's own ``kv_tiers`` spill tier so any preset machine works).
    ``background`` flows (repro.fabric.Flow, tier- or node-named
    endpoints) contend with the prefetch stream for shared links.
    ``weight``/``priority`` are the page flows' DMA QoS class (default:
    egalitarian best-effort; ``PagedKVCache.plan_prefetch`` raises it to
    the pager's deadline-critical class).

    ``page_bytes`` is the *logical* page size; with ``compression`` > 1
    each page crosses the wire at ``page_bytes / compression`` (the
    int8-cold-tier case — ``PagedKVCache.plan_prefetch`` passes its own
    ratio). Open-ended background flows (``nbytes == 0``) are materialized
    at ``background_nbytes`` — default: the plan's total wire bytes, i.e.
    the background streams at least as long as the prefetch (the
    historical heuristic, now an explicit knob).

    With no pages to fetch the plan is trivially empty — including on a
    degraded system whose spill tier was hot-removed (an evacuated cache
    must still schedule; its effective bandwidth reports 0.0).
    """
    from repro.fabric.systems import get_system
    from repro.transport import PageTransfer, Route, plan_transfers

    system = system or get_system("tpu_v5e")
    try:
        route = Route.resolve(system, src_tier or "host", system.compute)
        transfers = tuple(
            PageTransfer(p, page_bytes, compression=compression,
                         weight=weight, priority=priority) for p in pages)
        plan = plan_transfers(route, transfers, background=background,
                              background_nbytes=background_nbytes,
                              probe_weight=weight, probe_priority=priority,
                              tracer=tracer)
    except ValueError:
        # spill tier unreachable (hot-removed / dead link): only an empty
        # plan is schedulable — pages stranded there cannot be fetched
        if not pages:
            return PrefetchPlan((), {}, 0.0, 0.0)
        raise
    if tracer.enabled and pages:
        tracer.metrics.add("pager.prefetch.pages", len(pages))
        tracer.metrics.add("pager.prefetch.bytes", plan.wire_bytes,
                           tier="host")
    return PrefetchPlan(tuple(pages), dict(plan.eta), plan.total_time,
                        plan.effective_bw, plan)
