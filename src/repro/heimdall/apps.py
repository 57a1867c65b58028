"""HEIMDALL application benchmarks (paper §6) — one per paper experiment.

These exercise the real framework stack: the reduced-config LM decode loop
under different tier placements (Fig 21/23), the weighted-interleave serving
sweep (Fig 24), the offload-split sweep (Table 5) validated against the
cost model, the vector-DB top-k workload (Fig 25-27), and KV get/set
workloads (Fig 28-30).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ParallelConfig, ShapeConfig, get_config
from repro.heimdall.harness import Row, place, time_fn
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model


def _tiny_model(arch: str = "yi-9b"):
    cfg = get_config(arch).reduced(num_layers=4, d_model=128, head_dim=32,
                                   d_ff=256)
    mesh = make_host_mesh()
    model = Model.create(cfg, mesh, ParallelConfig(remat="none"))
    params = model.init(jax.random.key(0), dtype=jnp.bfloat16)
    return cfg, model, params


# -- Fig 21/23: decode tokens/s under tier placements ------------------------


def app_llm_inference(steps: int = 8, batch: int = 4,
                      prompt: int = 64) -> list:
    cfg, model, params = _tiny_model()
    rows = []
    tokens = jnp.ones((batch, prompt), jnp.int32)
    _, cache0 = model.prefill(params, {"tokens": tokens},
                              max_len=tokens.shape[1] + steps)

    decode = jax.jit(lambda p, c, t, i: model.decode(p, c, t, i),
                     donate_argnums=(1,))

    for tier in ("hbm", "host"):
        p_tier = jax.tree.map(lambda a: place(a, tier), params)

        def run():
            cache = jax.tree.map(jnp.copy, cache0)
            tok = jnp.ones((batch, 1), jnp.int32)
            for s in range(steps):
                if tier == "host":
                    p_dev = jax.tree.map(lambda a: place(a, "hbm"), p_tier)
                else:
                    p_dev = p_tier
                logits, cache = decode(p_dev, cache, tok, jnp.int32(prompt + s))
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return tok

        t = time_fn(run, warmup=1, iters=3)
        tps = steps * batch / t
        rows.append(Row(f"app_llm_inference/{tier}", t * 1e6,
                        f"tok_s={tps:.1f}"))
    return rows


# -- Table 5: offload-split sweep, validated against the cost model ------------


def app_offload_sweep(steps: int = 4, batch: int = 2) -> list:
    from repro.core.costmodel import offload_sweep
    cfg, model, params = _tiny_model()
    rows = []
    flat, tdef = jax.tree.flatten(params)
    sizes = [x.size * x.dtype.itemsize for x in flat]
    total = sum(sizes)
    tokens = jnp.ones((batch, 32), jnp.int32)
    _, cache0 = model.prefill(params, {"tokens": tokens},
                              max_len=tokens.shape[1] + steps)
    decode = jax.jit(lambda p, c, t, i: model.decode(p, c, t, i),
                     donate_argnums=(1,))

    for frac in (0.0, 0.5, 1.0):
        budget = total * frac
        placed, acc = [], 0
        for x, s in zip(flat, sizes):
            tier = "host" if acc < budget else "hbm"
            acc += s
            placed.append(place(x, tier))
        p_tier = jax.tree.unflatten(tdef, placed)

        def run():
            cache = jax.tree.map(jnp.copy, cache0)
            tok = jnp.ones((batch, 1), jnp.int32)
            for s in range(steps):
                p_dev = jax.tree.map(lambda a: place(a, "hbm"), p_tier)
                logits, cache = decode(p_dev, cache, tok, jnp.int32(32 + s))
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return tok

        t = time_fn(run, warmup=1, iters=3)
        rows.append(Row(f"app_offload_sweep/frac={frac}", t * 1e6,
                        f"tok_s={steps*batch/t:.1f}"))
    # cost-model reference curve (the paper's Table 5 shape)
    pts = offload_sweep(model_bytes=130 << 30, hbm_capacity=72 << 30,
                        link_bw=25 << 30, kv_bytes_per_seq=200 << 20,
                        flops_per_token=2 * 70e9, peak_flops=900e12,
                        hbm_bw=3 << 40, max_concurrency=150, n_points=5)
    for p in pts:
        rows.append(Row(f"app_offload_model/offload={p.offload_bytes>>30}GiB",
                        0.0, f"model_tok_s={p.tokens_per_s:.1f};{p.bound}"))
    return rows


# -- Fig 25-27: vector DB top-k ------------------------------------------------


def app_vectordb(n_vecs: int = 4096, dim: int = 128, k: int = 10,
                 queries: int = 16) -> list:
    rows = []
    rng = np.random.default_rng(0)
    db = jnp.asarray(rng.normal(size=(n_vecs, dim)), jnp.float32)
    qs = jnp.asarray(rng.normal(size=(queries, dim)), jnp.float32)

    @jax.jit
    def topk(db_, q_):
        sims = q_ @ db_.T
        return jax.lax.top_k(sims, k)

    for tier in ("hbm", "host"):
        db_t = place(db, tier)

        def run(q_):
            db_dev = place(db_t, "hbm") if tier == "host" else db_t
            return topk(db_dev, q_)

        t = time_fn(run, qs)
        rows.append(Row(f"app_vectordb/{tier}", t * 1e6,
                        f"qps={queries/t:.0f}"))
    return rows


# -- Fig 28-30: KV workload ------------------------------------------------------


def app_kv_workload(n_keys: int = 1 << 14, dim: int = 64,
                    ops: int = 1 << 10) -> list:
    rows = []
    rng = np.random.default_rng(0)
    store = jnp.asarray(rng.normal(size=(n_keys, dim)), jnp.float32)
    get_idx = jnp.asarray(rng.integers(0, n_keys, ops), jnp.int32)
    set_idx = jnp.asarray(rng.integers(0, n_keys, ops), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(ops, dim)), jnp.float32)

    @jax.jit
    def get(s, i):
        return s[i].sum()

    @jax.jit
    def set_(s, i, v):
        return s.at[i].set(v)

    for tier in ("hbm", "host"):
        s = place(store, tier)

        def get_t(s_, i):
            return get(place(s_, "hbm"), i)      # tier fetch + op

        def set_t(s_, i, v):
            return place(set_(place(s_, "hbm"), i, v), tier)

        tg = time_fn(get_t, s, get_idx)
        ts = time_fn(set_t, s, set_idx, vals)
        rows.append(Row(f"app_kv/{tier}/get", tg * 1e6,
                        f"ops_s={ops/tg:.0f}"))
        rows.append(Row(f"app_kv/{tier}/set", ts * 1e6,
                        f"ops_s={ops/ts:.0f}"))
    return rows


ALL_APPS = [app_llm_inference, app_offload_sweep, app_vectordb,
            app_kv_workload]
