"""Serving driver: batched request engine with tiered KV/weight placement.

Continuous-batching-lite: requests with different prompt lengths are padded
into a prefill batch, then decoded together; weights can live in HBM or be
streamed from host (StreamingParamServer — the beyond-paper double-buffered
mode whose win the cost model predicts via `overlap`).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --requests 4 --prompt 64 --gen 32 [--offload-weights]

Without ``--reduced`` the configuration runs at its published widths.

``DecodeScheduler`` is the deadline-aware decode loop over a tier-split
``PagedKVCache``: it plans host->HBM page prefetches through the fabric
simulator and admits each sequence into the decode batch at the first step
deadline by which *its* pages have landed (``PrefetchPlan.ready_by``),
instead of stalling the whole batch until the last page arrives. With the
pager's int8 cold tier the pages land ~2x sooner, which is exactly the win
``--paged-sim`` reports (fp16 vs int8, same page set, same contention):

  PYTHONPATH=src python -m repro.launch.serve --paged-sim \
      [--system tpu_v5e] [--requests 8] [--gen 32]

``--disagg-sim`` splits the engine's two roles across compute nodes:
prefill on one host, decode on another, KV pages shipped over the
contended fabric route ``repro.serving.disagg`` picks via the transport
layer — the disaggregated generalization of the same overlap story:

  PYTHONPATH=src python -m repro.launch.serve --disagg-sim \
      --system cxl_pool [--kv-dtype int8] [--trace-out disagg.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ParallelConfig, get_config
from repro.core.offload import put_tree
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.obs.trace import NULL_TRACER
from repro.runtime.fault import StragglerStats


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,) int32
    max_new: int


@dataclasses.dataclass
class Result:
    rid: int
    tokens: list
    prefill_ms: float
    decode_ms_per_tok: float
    logits: Optional[np.ndarray] = None   # (max_new, vocab) f32 if kept


class ServeEngine:
    def __init__(self, cfg, mesh=None,
                 parallel: ParallelConfig = ParallelConfig(fsdp=False),
                 offload_weights: bool = False, rng_seed: int = 0,
                 tracer=NULL_TRACER, slo=None):
        self.cfg = cfg
        # Observability: wall-clock prefill/decode-step spans plus, while
        # the tracer is enabled, a StragglerStats fed one sample per decode
        # step — its inflation flag and summary land in the metrics
        # snapshot, the signal the elastic-degradation loop will key on.
        # Every span, and each host phase of a call (``_phase``), also
        # lands in the JAX profiler's trace. ``slo`` optionally attaches
        # a repro.obs.SLOMonitor: one latency observation per finished
        # request (class "serve"), burn-rate alerting included.
        self.tracer = tracer
        self.slo = slo
        self.straggler = StragglerStats()
        _install_gc_spans()
        mesh = mesh or make_host_mesh()
        self.model = Model.create(cfg, mesh, parallel)
        params = self.model.init(jax.random.key(rng_seed),
                                 dtype=jnp.bfloat16)
        self.offload = offload_weights
        if offload_weights:
            self.params_home = put_tree(params, "pinned_host")
        else:
            self.params_home = params
        # the jitted closures capture the model, not the engine: a cycle
        # through self would keep the weights alive after the engine is
        # dropped, until the garbage collector happened to run. Their names
        # name the programs in the profiler's trace.
        model = self.model

        def serve_prefill(p, b, n):
            return model.prefill(p, b, max_len=n)

        def serve_decode_step(p, c, t, i):
            return model.decode(p, c, t, i)

        self._prefill = jax.jit(serve_prefill, static_argnums=(2,))
        self._decode = jax.jit(serve_decode_step, donate_argnums=(1,))

    def _params(self):
        """Paper-faithful sync fetch when offloaded (copy-on-demand)."""
        if self.offload:
            return put_tree(self.params_home, "device")
        return self.params_home

    def compile_prefill(self, requests: list[Request]):
        """The compiled prefill program this batch of requests runs (for
        inspecting what the compiler made of it, e.g. its kernels)."""
        toks = prompt_batch(requests)
        max_new = max(r.max_new for r in requests)
        return self._prefill.lower(self._params(),
                                   {"tokens": jnp.asarray(toks)},
                                   toks.shape[1] + max_new).compile()

    def prefill(self, requests: list[Request]) -> "PrefillHandoff":
        """The prefill role: run the prompt pass and hand off everything
        the decode role needs (KV cache, first tokens, step offsets).

        In a disaggregated deployment this runs on the prefill compute
        node and the returned handoff's KV pages are what crosses the
        fabric to the decode node (``repro.serving.disagg`` costs exactly
        that shipment); monolithic ``serve`` just passes it to ``decode``
        in-process.
        """
        B = len(requests)
        tracer = self.tracer
        plen = max(len(r.prompt) for r in requests)
        if tracer.enabled:
            for r in requests:
                tracer.instant("serve.admit", track=("serving", "engine"),
                               cat="serve", rid=r.rid,
                               prompt_len=len(r.prompt), max_new=r.max_new)
        t0 = time.perf_counter()
        max_new = max(r.max_new for r in requests)
        with tracer.span("serve.prefill", track=("serving", "engine"),
                         cat="serve", batch=B, prompt_len=plen):
            with _phase("serve.inputs"):
                params = self._params()
                batch = {"tokens": jnp.asarray(prompt_batch(requests))}
            with _phase("serve.dispatch"):
                logits, cache = self._prefill(params, batch, plen + max_new)
            with _phase("serve.sample"):
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            with _phase("serve.readback"):
                jax.block_until_ready(tok)
        prefill_ms = (time.perf_counter() - t0) * 1e3
        return PrefillHandoff(requests, cache, tok, plen, max_new,
                              prefill_ms, logits)

    def decode(self, handoff: "PrefillHandoff",
               keep_logits: bool = False) -> list[Result]:
        """The decode role: step the handed-off KV cache to completion.

        ``keep_logits`` copies each step's logits to the host into
        ``Result.logits`` (for checks against a reference; it costs a
        device read per step)."""
        requests = handoff.requests
        B = len(requests)
        tracer = self.tracer
        timed = tracer.enabled        # the straggler's one reader
        cache, tok = handoff.cache, handoff.tok
        outs = [[] for _ in requests]
        kept = []
        t0 = time.perf_counter()
        with _phase("serve.decode", batch=B, steps=handoff.max_new):
            for s in range(handoff.max_new):
                with tracer.span("serve.decode_step",
                                 track=("serving", "engine"), cat="serve",
                                 step=s, batch=B):
                    if timed:
                        ts = time.perf_counter()
                    with _phase("serve.inputs"):
                        params = self._params()
                        pos = jnp.int32(handoff.plen + s)
                    with _phase("serve.dispatch"):
                        logits, cache = self._decode(params, cache, tok, pos)
                    with _phase("serve.sample"):
                        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    with _phase("serve.readback"):
                        # one device read for the whole batch, not B
                        # scalar reads
                        tok_host = np.asarray(tok)
                        if keep_logits:
                            kept.append(np.asarray(logits[:, 0], np.float32))
                    if timed:
                        # sustained p95/median inflation of the step time
                        # is the elastic layer's degrade signal
                        self.straggler.record(time.perf_counter() - ts)
                    with _phase("serve.emit"):
                        for i in range(B):
                            outs[i].append(int(tok_host[i, 0]))
            jax.block_until_ready(tok)
        ms_per_tok = (time.perf_counter() - t0) * 1e3 / handoff.max_new
        if timed:
            m = tracer.metrics
            m.add("serve.requests", B)
            m.add("serve.decode_steps", handoff.max_new)
            m.add("serve.tokens_generated", B * handoff.max_new)
            m.set("serve.prefill_ms", handoff.prefill_ms)
            m.set("serve.decode_ms_per_tok", ms_per_tok)
            for k, v in self.straggler.summary().items():
                m.set(f"serve.straggler.{k}", v)
        if self.slo is not None:
            lat = (handoff.prefill_ms + ms_per_tok * handoff.max_new) * 1e-3
            for r in requests:
                self.slo.observe("serve", lat)
        return [Result(r.rid, outs[i][:r.max_new], handoff.prefill_ms,
                       ms_per_tok,
                       np.stack([s[i] for s in kept])[:r.max_new]
                       if keep_logits else None)
                for i, r in enumerate(requests)]

    def serve(self, requests: list[Request]) -> list[Result]:
        """Monolithic serving: prefill role then decode role, in-process
        (the synchronous-handoff special case of disaggregation)."""
        return self.decode(self.prefill(requests))


# A host phase of an engine call, on the profiler's clock only: the obs
# tracer's events stay the spans above. Named after what the host does.
_phase = jax.profiler.TraceAnnotation


class _GcSpans:
    """``gc.callbacks`` hook: a ``python.gc`` span around each collection
    of generation 1 or 2 (generation 0's are many and short). The hook is
    process-wide, as the collector is; a collection never nests in one."""

    def __init__(self):
        self.open = None

    def __call__(self, phase, info):
        if phase == "start" and info["generation"] > 0:
            self.open = _phase("python.gc", generation=info["generation"])
            self.open.__enter__()
        elif phase == "stop" and self.open is not None:
            span, self.open = self.open, None
            span.__exit__(None, None, None)


_GC_SPANS = _GcSpans()


def _install_gc_spans():
    if _GC_SPANS not in gc.callbacks:
        gc.callbacks.append(_GC_SPANS)


def prompt_batch(requests: list[Request]) -> np.ndarray:
    """(B, longest prompt) int32 token batch, prompts left-padded with 0."""
    plen = max(len(r.prompt) for r in requests)
    toks = np.zeros((len(requests), plen), np.int32)
    for i, r in enumerate(requests):
        toks[i, plen - len(r.prompt):] = r.prompt
    return toks


@dataclasses.dataclass
class PrefillHandoff:
    """What the prefill role produces and the decode role consumes — the
    unit that crosses the fabric when the roles live on different compute
    nodes."""
    requests: list               # the Requests this batch covers
    cache: object                # model KV cache (decode steps donate it)
    tok: jax.Array               # (B, 1) first sampled tokens
    plen: int                    # padded prompt length (step offset base)
    max_new: int
    prefill_ms: float
    logits: jax.Array            # (B, 1, vocab) last prompt position


# --------------------------------------------------------------------------
# Deadline-aware decode scheduling over the paged, tiered KV cache
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecodeStep:
    """One fired decode step of the scheduled loop."""
    step: int
    deadline: float              # when the step fires (s, sim time)
    seq_ids: tuple               # sequences decoded in this step's batch
    pages_resident: int          # host pages landed by the deadline


@dataclasses.dataclass(frozen=True)
class DecodeSchedule:
    """A simulated decode run: per-step batches + completion accounting."""
    steps: tuple                 # DecodeStep in firing order
    admit_time: dict             # seq id -> sim time it joined the batch
    finish_time: dict            # seq id -> sim time its last step is done
    makespan: float              # when the last sequence finishes (s)
    sync_makespan: float         # baseline: stall until ALL pages landed
    prefetch_total: float        # PrefetchPlan.total_time
    step_time: float
    violations: dict = dataclasses.field(default_factory=dict)
    # seq id -> overrun (s) past its deadline; only sequences given a
    # deadline via ``schedule(..., deadlines=)`` can appear here
    plan: object = None
    # the prefetch/transfer plan the schedule admitted against — the
    # drift sentinel replays it against calibration predictions

    @property
    def mean_completion(self) -> float:
        vals = list(self.finish_time.values())
        return sum(vals) / len(vals) if vals else 0.0

    @property
    def speedup(self) -> float:
        """Mean-latency win of deadline-aware admission: in the sync
        baseline every sequence waits for the WHOLE page set, so its mean
        completion equals the sync makespan; here each sequence finishes
        n_steps after its own pages landed."""
        return self.sync_makespan / max(self.mean_completion, 1e-18)


class DecodeScheduler:
    """Fires decode steps as prefetched pages land (PrefetchPlan.ready_by).

    The paper-faithful loop stalls every decode step until the whole page
    set is resident; this scheduler admits each sequence into the continuous
    batch at the first step deadline by which *its* host-tier pages have
    arrived, so sequences whose pages live in HBM (or landed early) decode
    while the slow-tier fetches are still in flight. With the pager's int8
    cold tier (``PagerConfig(kv_dtype="int8")``) every ETA is ~2x sooner —
    the bandwidth win turns directly into earlier admission. Page fetches
    ride the pager's DMA QoS class (high priority by default, overridable
    via ``priority``/``weight``): under a bulk background stream the
    prioritized ETAs — and with them every admission deadline — tighten
    toward the uncontended schedule.
    """

    def __init__(self, cache, *, system=None, background: tuple = (),
                 step_time: float = 500e-6, weight=None, priority=None,
                 tracer=NULL_TRACER):
        self.cache = cache
        self.system = system
        self.background = background
        self.step_time = float(step_time)
        self.weight = weight          # None -> pager's configured QoS class
        self.priority = priority
        # Observability: admission instants (with deadline slack), one
        # async request span admit->finish per sequence, and a B/E span
        # per fired decode step — all in sim time, so the exported trace
        # lines up with the fabric's per-link utilization tracks.
        self.tracer = tracer

    def ready_times(self, seq_ids: list, plan) -> dict:
        """Sim time each sequence's host pages are fully resident."""
        out = {}
        for s in seq_ids:
            pages = [p for p in self.cache.tables[s]
                     if self.cache.tier_of_page[p] == 1]
            out[s] = max((plan.eta[p] for p in pages), default=0.0)
        return out

    def schedule(self, seq_ids: list, n_steps: int,
                 deadlines: Optional[dict] = None) -> DecodeSchedule:
        """Simulate ``n_steps`` decode steps per sequence, admitting each
        sequence at its pages' arrival (deadline-aware continuous batch).

        ``deadlines`` optionally maps seq id -> SLO completion deadline
        (s, sim time). A sequence finishing after its deadline lands in
        ``DecodeSchedule.violations`` with its overrun — the interactive-
        class protection signal the degradation loop (and its no-reaction
        baseline) are judged on.
        """
        plan = self.cache.plan_prefetch(seq_ids, system=self.system,
                                        background=self.background,
                                        weight=self.weight,
                                        priority=self.priority)
        ready = self.ready_times(seq_ids, plan)
        seq_flows = None
        if self.tracer.enabled:
            # flow ids the pager's plan_transfers assigned ("page{p}") —
            # the per-request attribution joins these against the fabric
            # sim's flow lifecycle events
            seq_flows = {s: [f"page{p}" for p in self.cache.tables[s]
                             if self.cache.tier_of_page[p] == 1]
                         for s in seq_ids}
        return admission_schedule(ready, plan, n_steps, self.step_time,
                                  deadlines=deadlines,
                                  seq_flows=seq_flows, tracer=self.tracer)


def admission_schedule(ready: dict, plan, n_steps: int, step_time: float,
                       *, deadlines: Optional[dict] = None,
                       seq_flows: Optional[dict] = None,
                       starts: Optional[dict] = None,
                       prefill_done: Optional[dict] = None,
                       tracer=NULL_TRACER) -> DecodeSchedule:
    """The deadline-aware admission loop itself, plan-agnostic.

    ``ready`` maps seq id -> sim time its pages are fully resident (dict
    order is the admission preference order); ``plan`` is anything with
    ``ready_by(t)`` and ``total_time`` — a pager ``PrefetchPlan`` or a
    transport ``TransferPlan`` (the disaggregated prefill->decode shipment
    reuses this loop unchanged: pages landing over the cross-host route
    admit sequences exactly like host->HBM prefetches do).

    ``seq_flows`` (seq id -> list of fabric flow ids carrying its bytes)
    turns on per-request attribution: one ``attrib.request`` instant per
    sequence ties the request to its flows, its pages-ready time, its
    start (``starts``, default 0.0 — sim-time origin) and optionally its
    prefill completion (``prefill_done``), which is everything
    ``repro.obs.attribution`` needs to rebuild the critical path.
    """
    seq_ids = list(ready)
    if tracer.enabled and seq_flows is not None:
        for s in seq_ids:
            t0 = (starts or {}).get(s, 0.0)
            extra = {}
            pd = (prefill_done or {}).get(s)
            if pd is not None:
                extra["prefill_done"] = pd
            tracer.instant("attrib.request", ts=t0,
                           track=("scheduler", "attribution"),
                           cat="attrib", rid=s, start=t0, ready=ready[s],
                           flows=list(seq_flows.get(s, ())), **extra)
    remaining = {s: n_steps for s in seq_ids}
    admit: dict = {}
    finish: dict = {}
    steps = []
    t = min(ready.values()) if ready else 0.0
    k = 0
    traced = tracer.enabled
    while any(r > 0 for r in remaining.values()):
        resident = set(plan.ready_by(t))
        active = tuple(s for s in seq_ids
                       if remaining[s] > 0 and ready[s] <= t)
        if not active:                  # idle until the next arrival
            t = min(ready[s] for s in seq_ids if remaining[s] > 0)
            continue
        for s in active:
            if s not in admit:
                admit[s] = t
                if traced:
                    # slack: how long the sequence sat decode-ready
                    # (pages landed at ready[s]) before the step grid
                    # admitted it — deadline-alignment cost, not fabric
                    tracer.instant(
                        "sched.admit", ts=t,
                        track=("scheduler", "admissions"), cat="sched",
                        seq=s, ready=ready[s],
                        deadline_slack=t - ready[s])
                    tracer.async_begin(
                        f"seq{s}", id=f"seq{s}", ts=t,
                        track=("scheduler", "requests"), cat="sched",
                        seq=s, n_steps=n_steps)
            remaining[s] -= 1
            if remaining[s] == 0:
                finish[s] = t + step_time
                if traced:
                    tracer.async_end(
                        f"seq{s}", id=f"seq{s}", ts=finish[s],
                        track=("scheduler", "requests"), cat="sched",
                        completion=finish[s])
        steps.append(DecodeStep(k, t, active, len(resident)))
        if traced:
            tracer.begin("sched.step", ts=t,
                         track=("scheduler", "steps"), cat="sched",
                         step=k, batch=len(active),
                         pages_resident=len(resident))
            tracer.end("sched.step", ts=t + step_time,
                       track=("scheduler", "steps"), cat="sched")
        k += 1
        t += step_time
    makespan = max(finish.values()) if finish else 0.0
    sync = plan.total_time + n_steps * step_time
    violations = {}
    if deadlines:
        for s, dl in deadlines.items():
            done = finish.get(s)
            if done is not None and done > dl:
                violations[s] = done - dl
    sched = DecodeSchedule(tuple(steps), admit, finish, makespan, sync,
                           plan.total_time, step_time, violations,
                           plan=plan)
    if traced:
        m = tracer.metrics
        m.add("sched.steps", len(steps))
        m.add("sched.sequences", len(seq_ids))
        m.set("sched.makespan_s", makespan)
        m.set("sched.mean_completion_s", sched.mean_completion)
        m.set("sched.prefetch_total_s", plan.total_time)
        if deadlines:
            m.add("sched.deadline_violations", len(violations))
            for s, over in violations.items():
                tracer.instant("sched.deadline_miss",
                               ts=finish[s],
                               track=("scheduler", "admissions"),
                               cat="sched", seq=s, overrun_s=over)
    return sched


def paired_kv_caches(*, requests: int = 8, tokens: int = 1056,
                     page_size: int = 64, kv_heads: int = 8,
                     head_dim: int = 128, weights: tuple = (2, 1)) -> dict:
    """{'fp16': pager, 'int8': pager} with identical placement and fill —
    the 'same page set' premise every fp-vs-int8 ratio rests on lives in
    exactly one place (the kv_quant benchmark family reuses this)."""
    from repro.serving.pager import PagedKVCache, PagerConfig
    n_pages = max(64, requests * (-(-tokens // page_size)) + 8)
    kv = jnp.zeros((tokens, kv_heads, head_dim), jnp.bfloat16)
    caches = {}
    for label, kv_dtype in (("fp16", None), ("int8", "int8")):
        c = PagedKVCache(PagerConfig(
            page_size=page_size, n_pages=n_pages, kv_heads=kv_heads,
            head_dim=head_dim, weights=weights, dtype="bfloat16",
            kv_dtype=kv_dtype))
        for s in range(requests):
            c.allocate(s)
            c.append(s, kv, kv)
        caches[label] = c
    return caches


def simulate_paged_decode(*, requests: int = 8, prompt: int = 1024,
                          gen: int = 32, page_size: int = 64,
                          kv_heads: int = 8, head_dim: int = 128,
                          weights: tuple = (2, 1), system_name: str =
                          "tpu_v5e", step_us: float = 100.0,
                          with_background: bool = True,
                          prefetch_priority: int = 0,
                          calibration_profile=None,
                          tracer=NULL_TRACER) -> dict:
    """fp16-vs-int8 decode scheduling comparison on one page set.

    Builds two pagers with identical page placement — one bf16, one with
    the int8 cold tier — fills them with the same sequences, and schedules
    the same decode run against the same background traffic. The report is
    the headline benchmark: bytes over the host link, simulated contended
    prefetch completion, and decode makespan.

    ``prefetch_priority`` defaults to 0 (egalitarian): this report's
    premise is the *contended* regime the kv_quant family baselined in
    PR 2; raise it to see the DMA-QoS regime (the qos family's territory).

    ``calibration_profile`` (a ``repro.calibrate.CalibrationProfile`` or a
    path to its JSON artifact) swaps the nominal preset for the calibrated
    machine — every ETA and admission deadline then rests on *fitted* link
    constants instead of datasheet numbers (the serve half of the
    run -> fit -> validate -> serve loop).

    An enabled ``tracer`` records both runs into one trace, each scoped by
    label — the fp16 run's fabric tracks live under process
    ``"fp16/fabric"``, the int8 run's under ``"int8/fabric"`` — so the two
    contended prefetches can be compared side by side in Perfetto; the
    metrics snapshot is embedded in the report under ``"metrics"``.
    """
    from repro.fabric.contention import Flow
    from repro.fabric.systems import from_profile, get_system

    if calibration_profile is not None:
        from repro.calibrate import CalibrationProfile
        if isinstance(calibration_profile, str):
            calibration_profile = CalibrationProfile.load(
                calibration_profile)
        system = from_profile(calibration_profile, preset=system_name)
    else:
        system = get_system(system_name)
    # fixed-size background stream: both the fp16 and int8 runs must see
    # IDENTICAL contention (an open-ended flow would be auto-sized from
    # each cache's own page bytes, quietly shrinking the int8 background)
    bg = (Flow("offload", "host", "hbm", nbytes=256 << 20),) \
        if with_background else ()
    toks = prompt + gen
    out = {"system": system_name, "requests": requests,
           "tokens_per_seq": toks, "step_us": step_us,
           "background": bool(with_background),
           "calibrated": calibration_profile is not None}
    caches = paired_kv_caches(requests=requests, tokens=toks,
                              page_size=page_size, kv_heads=kv_heads,
                              head_dim=head_dim, weights=weights)
    for label, cache in caches.items():
        seqs = list(range(requests))
        sub = tracer.scoped(label, run=label)
        cache.tracer = sub            # pager spans + fabric sim timelines
        sched = DecodeScheduler(cache, system=system, background=bg,
                                step_time=step_us * 1e-6,
                                priority=prefetch_priority, tracer=sub)
        ds = sched.schedule(seqs, gen)
        n_host = len(cache.host_pages(seqs))
        out[label] = {
            "host_pages": n_host,
            "page_bytes": cache.host_page_bytes,
            "host_link_bytes": n_host * cache.host_page_bytes,
            "prefetch_total_s": ds.prefetch_total,
            "mean_completion_s": ds.mean_completion,
            "decode_makespan_s": ds.makespan,
            "sync_makespan_s": ds.sync_makespan,
            "overlap_speedup": round(ds.speedup, 3),
            "first_admit_s": min(ds.admit_time.values(), default=0.0),
        }
    fp, q = out["fp16"], out["int8"]
    out["bytes_reduction"] = round(
        fp["host_link_bytes"] / max(q["host_link_bytes"], 1), 3)
    out["prefetch_speedup"] = round(
        fp["prefetch_total_s"] / max(q["prefetch_total_s"], 1e-18), 3)
    out["decode_latency_speedup"] = round(
        fp["mean_completion_s"] / max(q["mean_completion_s"], 1e-18), 3)
    if tracer.enabled:
        out["metrics"] = tracer.metrics.to_json()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="run the configuration's tiny CPU-test variant "
                         "instead of its published widths")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--offload-weights", action="store_true")
    ap.add_argument("--paged-sim", action="store_true",
                    help="simulated fp16-vs-int8 paged decode scheduling "
                         "report (no model run)")
    ap.add_argument("--disagg-sim", action="store_true",
                    help="simulated disaggregated prefill/decode serve: "
                         "roles on separate compute nodes, KV pages "
                         "shipped over the contended fabric route the "
                         "cost model picks (no model run)")
    ap.add_argument("--kv-dtype", default=None, choices=["int8"],
                    help="ship pages in the pager's quantized cold-tier "
                         "layout (--disagg-sim)")
    ap.add_argument("--degrade-sim", action="store_true",
                    help="inject the headline degradation (host link "
                         "halved mid-serve) and report the reacting run "
                         "vs the no-reaction baseline (no model run)")
    ap.add_argument("--degrade-factor", type=float, default=0.5,
                    help="surviving bandwidth fraction for --degrade-sim")
    ap.add_argument("--degrade-round", type=int, default=4,
                    help="serve round the fault fires at (--degrade-sim)")
    ap.add_argument("--system", default="tpu_v5e")
    ap.add_argument("--step-us", type=float, default=100.0)
    ap.add_argument("--calibration-profile", default=None,
                    help="path to a CalibrationProfile JSON; the paged-sim "
                         "then plans on fitted link constants")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="write a Chrome trace-event file (open in "
                         "https://ui.perfetto.dev) covering the run: "
                         "per-link utilization tracks, flow lifecycles, "
                         "pager and scheduler/engine spans")
    ap.add_argument("--metrics-out", default=None, metavar="METRICS.json",
                    help="write the metrics snapshot "
                         "(MetricsRegistry.to_json) alongside the report")
    ap.add_argument("--recorder-out", default=None, metavar="FLIGHT.json",
                    help="attach a FlightRecorder (bounded ring buffer) "
                         "and write its snapshot here — for --degrade-sim "
                         "the dump is triggered by the first SLO burn "
                         "alert / detector fire and carries the failing "
                         "window's attribution summary")
    ap.add_argument("--recorder-capacity", type=int, default=8192,
                    help="flight-recorder ring size in events")
    ap.add_argument("--openmetrics-out", default=None,
                    metavar="METRICS.txt",
                    help="write an OpenMetrics text exposition snapshot: "
                         "metric counters/gauges plus the bandwidth "
                         "ledger's per-(link, QoS, purpose, request "
                         "class) byte charges and per-link efficiency")
    ap.add_argument("--metrics-listen", default=None, metavar="HOST:PORT",
                    help="after the run, serve the same OpenMetrics "
                         "snapshot over HTTP at /metrics until "
                         "interrupted (a scrape endpoint)")
    ap.add_argument("--recalibrate", action="store_true",
                    help="close the drift loop in --degrade-sim: a "
                         "DriftSentinel flag triggers a single-route "
                         "re-probe + refit + hot-swap (needs "
                         "--calibration-profile)")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = NULL_TRACER
    if args.trace_out or args.metrics_out or args.openmetrics_out \
            or args.metrics_listen:
        from repro.obs import Tracer
        tracer = Tracer()
    recorder = None
    if args.recorder_out:
        from repro.obs import FlightRecorder
        # events flow through the ring; an enabled full tracer (from
        # --trace-out/--metrics-out) still sees everything via forward=
        recorder = FlightRecorder(
            capacity=args.recorder_capacity,
            forward=tracer if tracer.enabled else None)
        tracer = recorder

    def _render_openmetrics():
        from repro.obs import BandwidthLedger, openmetrics_text
        full = recorder.forward if (recorder is not None
                                    and recorder.forward is not None) \
            else tracer
        return openmetrics_text(metrics=tracer.metrics,
                                ledger=BandwidthLedger.from_tracer(full))

    def _flush_obs():
        # --trace-out wants the full history: the forwarded tracer when a
        # ring-buffer recorder sits in front, the tracer itself otherwise
        full = recorder.forward if (recorder is not None
                                    and recorder.forward is not None) \
            else tracer
        if args.trace_out:
            from repro.obs import write_chrome_trace
            write_chrome_trace(full, args.trace_out)
            print(f"# trace: {args.trace_out} "
                  f"({len(full.events)} events; open in "
                  "https://ui.perfetto.dev)")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(tracer.metrics.to_json(), f, indent=2,
                          sort_keys=True)
            print(f"# metrics: {args.metrics_out}")
        if args.recorder_out:
            trace = recorder.dump(args.recorder_out)
            meta = trace.get("metadata", {})
            print(f"# flight recorder: {args.recorder_out} "
                  f"(reason={meta.get('reason')!r}, "
                  f"{meta.get('events')} events, "
                  f"{meta.get('dropped')} dropped; open in "
                  "https://ui.perfetto.dev)")
        if args.openmetrics_out:
            from repro.obs import write_openmetrics
            write_openmetrics(args.openmetrics_out, _render_openmetrics())
            print(f"# openmetrics: {args.openmetrics_out}")
        if args.metrics_listen:
            import time as _time
            host, _, port = args.metrics_listen.rpartition(":")
            from repro.obs import serve_openmetrics
            server = serve_openmetrics(_render_openmetrics,
                                       host=host or "127.0.0.1",
                                       port=int(port))
            print(f"# metrics: http://{host or '127.0.0.1'}:"
                  f"{server.server_port}/metrics (Ctrl-C to stop)")
            try:
                while True:
                    _time.sleep(3600)
            except KeyboardInterrupt:
                server.shutdown()

    if args.paged_sim:
        print(json.dumps(simulate_paged_decode(
            requests=args.requests, gen=args.gen,
            system_name=args.system, step_us=args.step_us,
            calibration_profile=args.calibration_profile,
            tracer=tracer), indent=2))
        _flush_obs()
        return

    if args.disagg_sim:
        from repro.serving.disagg import DisaggConfig, run_disagg_serve
        report = run_disagg_serve(
            DisaggConfig(system=args.system, requests=args.requests,
                         prompt=args.prompt, gen=args.gen,
                         step_us=args.step_us, kv_dtype=args.kv_dtype),
            calibration_profile=args.calibration_profile, tracer=tracer)
        print(json.dumps(report.to_json(), indent=2))
        _flush_obs()
        return

    if args.degrade_sim:
        from repro.runtime.degrade import (DegradedServeConfig,
                                           host_link_degraded,
                                           run_degraded_serve)
        cfg = DegradedServeConfig(system=args.system,
                                  step_us=args.step_us)
        sched = host_link_degraded(system=args.system,
                                   at_round=args.degrade_round,
                                   factor=args.degrade_factor)
        sentinel = None
        if args.recalibrate:
            if not args.calibration_profile:
                ap.error("--recalibrate needs --calibration-profile "
                         "(the drift sentinel's expectation and the "
                         "recalibrator's profile to hot-swap)")
            from repro.calibrate import CalibrationProfile
            from repro.obs import DriftSentinel
            prof = CalibrationProfile.load(args.calibration_profile)
            sentinel = DriftSentinel(
                prof, preset=args.system,
                tracer=(tracer.scoped("react")
                        if tracer.enabled else tracer))
        react = run_degraded_serve(
            sched, cfg=cfg, react=True,
            calibration_profile=args.calibration_profile,
            sentinel=sentinel, recalibrate=args.recalibrate,
            tracer=tracer.scoped("react") if tracer.enabled else tracer,
            recorder=recorder)
        base = run_degraded_serve(
            sched, cfg=cfg, react=False,
            calibration_profile=args.calibration_profile,
            tracer=tracer.scoped("baseline") if tracer.enabled else tracer)
        print(json.dumps({"react": react.to_json(),
                          "baseline": base.to_json()}, indent=2))
        _flush_obs()
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    engine = ServeEngine(cfg, offload_weights=args.offload_weights,
                         tracer=tracer)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    args.prompt - (i % 4)).astype(np.int32),
                    args.gen) for i in range(args.requests)]
    results = engine.serve(reqs)
    tps = args.requests * args.gen / (results[0].decode_ms_per_tok
                                      * args.gen / 1e3)
    print(json.dumps({
        "requests": len(results),
        "prefill_ms": round(results[0].prefill_ms, 1),
        "decode_ms_per_tok": round(results[0].decode_ms_per_tok, 2),
        "tokens_per_s": round(tps, 1),
        "offloaded": args.offload_weights,
        "sample": results[0].tokens[:8],
    }))
    _flush_obs()


if __name__ == "__main__":
    main()
