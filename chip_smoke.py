#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU, at yi-9b's published widths.

    python chip_smoke.py               # one chip: serve + tiered-KV phases
    python chip_smoke.py --four-chips  # four chips: 48-layer yi-9b sharded
                                       # over model=4, plus 1-vs-4 check

One process holds the chip and runs the phases in order; any failed check
exits non-zero. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The numbers printed before it are smoke readings, not benchmark results.

Depth cut: one v5e chip (16 GB) holds 24 of yi-9b's 48 layers in bf16
(9.35 GB of weights), which is one chip's share of a two-stage pipeline.
Every width is as published: d_model 4096, 32 query / 4 kv heads of 128,
d_ff 11008, vocab 64000. Weights are random, drawn from ``--seed``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent
sys.path.insert(0, str(CHECKOUT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config.base import ParallelConfig, get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, make_mesh  # noqa: E402
from repro.launch.serve import (Request, ServeEngine,  # noqa: E402
                                prompt_batch)

SERVE_LAYERS = 24      # of 48: one chip's share of a two-stage pipeline
PARALLEL = ParallelConfig(fsdp=False, attention_kernel="pallas")
KERNEL_MARK = "tpu_custom_call"
PAGER_MIN_BYTES = 256 << 20   # host-link bytes each tiered-KV run moves

# Logit agreement, bf16. bf16 keeps 8 significant bits (relative rounding
# 2**-9). The engine's prefill (flash kernel) and decode (cached attention)
# round in other places than the reference's full forward (chunked XLA
# attention), and the differences add up over the residual stream's layers.
# Both limits are relative to the reference logits' RMS: at most 1/8 of it
# for the worst logit, at most 1/64 of it on average. A float8 path
# (3 significant bits, rounding 2**-4) already misses the mean limit.
TOL_MAX = 1 / 8
TOL_MEAN = 1 / 64


class SmokeError(Exception):
    """A check of the smoke run failed."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def reading(name: str, value) -> None:
    print(f"smoke reading: {name} = {value}", flush=True)


def yi9b(layers: int):
    return dataclasses.replace(get_config("yi-9b"), num_layers=layers)


def make_requests(cfg, batch: int, prompt: int, gen: int, seed: int):
    """``batch`` requests, prompt lengths ``prompt`` down to prompt-3 (so the
    engine left-pads them to ``prompt``), each asking ``gen`` tokens."""
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, prompt - i % 4)
                    .astype(np.int32), gen) for i in range(batch)]


def logit_error(got: np.ndarray, ref: np.ndarray) -> dict:
    d = np.abs(got.astype(np.float64) - ref.astype(np.float64))
    rms = float(np.sqrt(np.mean(np.square(ref.astype(np.float64)))))
    return {"max_over_rms": float(d.max()) / rms,
            "mean_over_rms": float(d.mean()) / rms}


def check_logits(name: str, got, ref) -> dict:
    err = logit_error(np.asarray(got), np.asarray(ref))
    reading(f"{name}.max_abs_err_over_rms",
            f"{err['max_over_rms']:.6g} (limit {TOL_MAX:.6g})")
    reading(f"{name}.mean_abs_err_over_rms",
            f"{err['mean_over_rms']:.6g} (limit {TOL_MEAN:.6g})")
    check(err["max_over_rms"] <= TOL_MAX and err["mean_over_rms"] <= TOL_MEAN,
          f"{name}: logits disagree beyond the bf16 tolerance: {err}")
    return err


def _q_chunk(n: int, cap: int = 512) -> int:
    return max(c for c in range(1, min(cap, n) + 1) if n % c == 0)


def teacher_forced_logits(engine: ServeEngine, tokens: np.ndarray,
                          n_last: int) -> np.ndarray:
    """Full forward (XLA attention, no cache) over ``tokens``; logits of
    the last ``n_last`` positions, (B, n_last, vocab) f32."""
    from repro.models.layers import unembed
    from repro.models.model import Model
    from repro.models.transformer import forward_hidden
    m = Model.create(engine.cfg, engine.model.mctx.mesh,
                     ParallelConfig(fsdp=False))
    qc = _q_chunk(tokens.shape[1])

    @jax.jit
    def fwd(params, toks):
        x, _, _ = forward_hidden(params, m.cfg, m.mctx, {"tokens": toks},
                                 q_chunk=qc)
        return unembed(params["embed"], x[:, -n_last:],
                       m.cfg.tie_embeddings).astype(jnp.float32)
    return np.asarray(fwd(engine.params_home, jnp.asarray(tokens)))


def serve_phase(cfg, devices, *, batch: int = 8, prompt: int = 1024,
                gen: int = 32, seed: int = 0) -> dict:
    """``ServeEngine`` serves ``batch`` requests on a mesh of ``devices``;
    decode logits are checked against a teacher-forced full forward."""
    mesh = make_mesh((1, len(devices)), (DATA_AXIS, MODEL_AXIS), devices)
    on_tpu = devices[0].platform == "tpu"
    t0 = time.perf_counter()
    engine = ServeEngine(cfg, mesh=mesh, parallel=PARALLEL, rng_seed=seed)
    jax.block_until_ready(engine.params_home)
    reading("serve.init_s", time.perf_counter() - t0)
    reqs = make_requests(cfg, batch, prompt, gen, seed)

    t0 = time.perf_counter()
    compiled = engine.compile_prefill(reqs)
    reading("serve.prefill_compile_s", time.perf_counter() - t0)
    if on_tpu:
        check(KERNEL_MARK in compiled.as_text(),
              "compiled prefill holds no Pallas kernel: flash attention "
              "was not compiled for the chip")

    # run 1 compiles decode and keeps every step's logits for the check
    t0 = time.perf_counter()
    handoff = engine.prefill(reqs)
    results = engine.decode(handoff, keep_logits=True)
    reading("serve.first_run_s (includes decode compile)",
            time.perf_counter() - t0)
    toks = np.array([r.tokens for r in results], np.int32)      # (B, gen)
    check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
          "a generated token id lies outside [0, vocab)")
    first = np.asarray(handoff.tok, np.int32)                   # (B, 1)
    fed = np.concatenate([prompt_batch(reqs), first, toks[:, :-1]], axis=1)
    ref = teacher_forced_logits(engine, fed, gen + 1)
    check_logits("serve.prefill_logits",
                 np.asarray(handoff.logits[:, 0], np.float32), ref[:, 0])
    dec = np.stack([r.logits for r in results])                 # (B,gen,V)
    err = check_logits("serve.decode_logits", dec, ref[:, 1:])

    # run 2: the timed one, all programs compiled
    results = engine.serve(reqs)
    reading("serve.prefill_ms", results[0].prefill_ms)
    reading("serve.decode_ms_per_token", results[0].decode_ms_per_tok)
    if on_tpu:
        peak = devices[0].memory_stats().get("peak_bytes_in_use")
        reading("serve.peak_bytes_in_use", peak)
    return {"tokens": toks, "decode_err": err}


def tiered_kv_phase(*, n_pages: int = 8192, page: int = 64,
                    kv_heads: int = 4, head_dim: int = 128,
                    q_heads: int = 32, n_seqs: int = 16, seed: int = 0,
                    min_bytes: int = PAGER_MIN_BYTES) -> dict:
    """``PagedKVCache`` with half its pages on the host tier: append,
    spill, fetch and attend, fp and then int8, each against its reference.
    """
    from repro.kernels import default_interpret
    from repro.kernels.paged_attention import (paged_attention_quant_ref,
                                               paged_attention_ref)
    from repro.kernels.paged_attention import kernel as pk
    from repro.kernels.quant import quantize_pages
    from repro.serving.pager import PagedKVCache, PagerConfig
    on_tpu = jax.devices()[0].platform == "tpu"
    per_seq = n_pages * page // n_seqs
    key = jax.random.key(seed)
    out = {}
    for kv_dtype in (None, "int8"):
        label = kv_dtype or "bf16"
        cache = PagedKVCache(PagerConfig(
            page_size=page, n_pages=n_pages, kv_heads=kv_heads,
            head_dim=head_dim, weights=(1, 1), dtype="bfloat16",
            kv_dtype=kv_dtype))
        for s in range(n_seqs):
            kk, kv_, key = jax.random.split(key, 3)
            shape = (per_seq, kv_heads, head_dim)
            cache.allocate(s)
            cache.append(s, jax.random.normal(kk, shape, jnp.bfloat16),
                         jax.random.normal(kv_, shape, jnp.bfloat16))
        k_live, v_live = cache.k_pool, cache.v_pool
        jax.block_until_ready((k_live, v_live))

        # two round trips: the first compiles and pins host buffers, the
        # second is the one whose times are read
        for _ in range(2):
            t0 = time.perf_counter()
            n = cache.spill_cold_pages()
            jax.block_until_ready((cache.k_pool_host, cache.v_pool_host))
            spill_s = time.perf_counter() - t0
            host = [cache.k_pool_host, cache.v_pool_host]
            if kv_dtype == "int8":
                host += [cache.k_scales_host, cache.v_scales_host]
            check(all(a.sharding.memory_kind == "pinned_host" for a in host),
                  f"{label}: host pools are not in pinned_host memory: "
                  f"{[a.sharding.memory_kind for a in host]}")
            del host
            t0 = time.perf_counter()
            cache.fetch_spilled()
            jax.block_until_ready((cache.k_pool, cache.v_pool))
            fetch_s = time.perf_counter() - t0
        nbytes = n * cache.host_page_bytes
        check(nbytes >= min_bytes,
              f"{label}: only {nbytes} bytes crossed the host link")
        reading(f"pager.{label}.host_pages", n)
        reading(f"pager.{label}.bytes_each_way", nbytes)
        reading(f"pager.{label}.spill_ms", spill_s * 1e3)
        reading(f"pager.{label}.fetch_ms", fetch_s * 1e3)
        reading(f"pager.{label}.spill_GB_per_s", nbytes / spill_s / 1e9)
        reading(f"pager.{label}.fetch_GB_per_s", nbytes / fetch_s / 1e9)

        idx = jnp.asarray(np.nonzero(cache.tier_of_page == 1)[0])
        for live, now, name in ((k_live, cache.k_pool, "k"),
                                (v_live, cache.v_pool, "v")):
            if kv_dtype is None:
                check(bool(jnp.array_equal(live, now)),
                      f"{label}: {name} pages changed in the round trip")
            else:
                # per (page, kv head): half a quantization step
                # (absmax/254) plus the bf16 rounding of the result
                a = jnp.take(live, idx, 0).astype(jnp.float32)
                b = jnp.take(now, idx, 0).astype(jnp.float32)
                absmax = jnp.max(jnp.abs(a), axis=(1, 3), keepdims=True)
                bound = absmax / 254 + jnp.abs(a) * 2.0 ** -8 + 1e-6
                check(bool(jnp.all(jnp.abs(a - b) <= bound)),
                      f"int8: {name} pages exceed the quantization bound")

        seqs = list(range(min(8, n_seqs)))
        q = jax.random.normal(key, (len(seqs), q_heads, head_dim),
                              jnp.bfloat16)
        bt, lens = cache.block_table(seqs)
        interpret = default_interpret(None)
        with jax.default_matmul_precision("highest"):
            if kv_dtype is None:
                got = cache.attend(q, seqs)
                ref = paged_attention_ref(q, cache.k_pool, cache.v_pool,
                                          bt, lens)
                prog = pk.paged_attention.lower(
                    q, cache.k_pool, cache.v_pool, bt, lens,
                    interpret=interpret)
            else:
                got = cache.attend_quant(q, seqs)
                (kq, ks), (vq, vs) = (quantize_pages(cache.k_pool),
                                      quantize_pages(cache.v_pool))
                ref = paged_attention_quant_ref(q, kq, vq, ks, vs, bt, lens)
                prog = pk.paged_attention_quant.lower(
                    q, kq, vq, ks, vs, bt, lens, interpret=interpret)
        diff = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - ref.astype(jnp.float32))))
        reading(f"pager.{label}.attend_max_abs_err", diff)
        check(diff <= 2e-2, f"{label}: paged attention disagrees with its "
              f"reference by {diff}")
        if on_tpu:
            check(KERNEL_MARK in prog.compile().as_text(),
                  f"{label}: paged attention is not a compiled kernel")
        out[label] = {"host_pages": n, "bytes": nbytes, "attend_err": diff}
        del cache, k_live, v_live
    return out


def weight_bytes_per_device(params) -> dict:
    per = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
    return per


def four_chip_phase(devices, *, base=None, layers: int = 48,
                    cmp_layers: int = 8, batch: int = 8, prompt: int = 1024,
                    gen: int = 16, seed: int = 0) -> dict:
    """Full-depth yi-9b sharded over a (data=1, model=4) mesh serves
    ``batch`` requests; then ``cmp_layers``-layer yi-9b, same seed, on one
    chip and on the four-chip mesh must agree."""
    check(len(devices) >= 4, f"--four-chips needs 4 devices, JAX found "
          f"{len(devices)}")
    base = base or get_config("yi-9b")
    mesh4 = make_mesh((1, 4), (DATA_AXIS, MODEL_AXIS), devices[:4])
    mesh1 = make_mesh((1, 1), (DATA_AXIS, MODEL_AXIS), devices[:1])
    cfg = dataclasses.replace(base, num_layers=layers)
    reqs = make_requests(cfg, batch, prompt, gen, seed)

    engine = ServeEngine(cfg, mesh=mesh4, parallel=PARALLEL, rng_seed=seed)
    per = weight_bytes_per_device(engine.params_home)
    total = sum(jax.tree.leaves(jax.tree.map(lambda a: a.nbytes,
                                             engine.params_home)))
    reading("four.layers", layers)
    reading("four.model_weight_bytes", total)
    for d, nb in sorted(per.items(), key=lambda kv: kv[0].id):
        reading(f"four.weight_bytes[device {d.id}]", nb)
    check(len(per) == 4 and max(per.values()) < total / 2,
          "the model's weights are not split over four chips")
    t0 = time.perf_counter()
    engine.serve(reqs)
    reading("four.first_run_s (includes compile)", time.perf_counter() - t0)
    results = engine.serve(reqs)
    toks = np.array([r.tokens for r in results])
    check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
          "a generated token id lies outside [0, vocab)")
    reading("four.prefill_ms", results[0].prefill_ms)
    reading("four.decode_ms_per_token", results[0].decode_ms_per_tok)
    del engine, results
    gc.collect()

    cfg_c = dataclasses.replace(base, num_layers=cmp_layers)
    runs = {}
    for name, mesh in (("one", mesh1), ("four", mesh4)):
        e = ServeEngine(cfg_c, mesh=mesh, parallel=PARALLEL, rng_seed=seed)
        h = e.prefill(reqs)
        rs = e.decode(h)
        # greedy tokens: the prefill's first one, then every decode step's
        runs[name] = (np.asarray(h.logits[:, 0], np.float32),
                      np.concatenate([np.asarray(h.tok),
                                      [r.tokens for r in rs]], axis=1))
        del e, h, rs
        gc.collect()
    (l1, t1), (l4, t4) = runs["one"], runs["four"]
    err = check_logits("compare.last_token_logits", l4, l1)
    differ = np.argwhere(t1 != t4)
    reading("compare.greedy_tokens", "identical" if differ.size == 0 else
            f"first differ at (request, step) {tuple(differ[0])}")
    check(differ.size == 0, "greedy tokens differ between one chip and four")
    return {"per_device": per, "total": total, "compare_err": err}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (48 layers on a "
                         "model=4 mesh, and its 1-vs-4 comparison)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {len(devices)} "
              f"{dev.platform} device(s) ({dev.device_kind})",
              file=sys.stderr)
        return 1
    reading("compile_cache_dir", enable_compile_cache(CHECKOUT))
    reading("device", f"{dev.platform} {dev.device_kind} x{len(devices)}")
    try:
        if args.four_chips:
            four_chip_phase(devices, seed=args.seed)
        else:
            print(f"# serve phase: yi-9b at published widths, "
                  f"{SERVE_LAYERS} of 48 layers (one chip's share of a "
                  f"two-stage pipeline)", flush=True)
            serve_phase(yi9b(SERVE_LAYERS), devices[:1], seed=args.seed)
            gc.collect()        # the engine's weights go before the pager's
            print("# tiered-KV phase: PagedKVCache, half the pages on the "
                  "host tier, fp then int8", flush=True)
            tiered_kv_phase(seed=args.seed)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
