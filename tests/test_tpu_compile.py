"""Compile guards: the main-path Pallas kernels, compiled by the TPU compiler
for a described (not attached) TPU v5e chip at yi-9b's widths.

Interpret-mode tests cannot see the compiler's tiling and VMEM checks; these
compiles can, at no chip time. The topology is described inside a fixture
(never at import), because only one process at a time may load the TPU
library. Keep every described-chip test in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.paged_attention.kernel import (paged_attention,
                                                  paged_attention_quant)
from repro.kernels.quant.kernel import dequantize_pages, quantize_pages

# yi-9b page geometry: 32 query heads over 4 kv heads of 128, 64-token pages
B, HQ, HKV, D, PAGE, N_PAGES, PPS = 8, 32, 4, 128, 64, 1024, 16
PROMPT = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles(one_chip):
    args = (_spec((B, HQ, D), "bfloat16", one_chip),
            _spec((N_PAGES, PAGE, HKV, D), "bfloat16", one_chip),
            _spec((N_PAGES, PAGE, HKV, D), "bfloat16", one_chip),
            _spec((B, PPS), "int32", one_chip),
            _spec((B,), "int32", one_chip))
    _assert_kernel(paged_attention.lower(*args, interpret=False).compile())


def test_paged_attention_quant_compiles(one_chip):
    args = (_spec((B, HQ, D), "bfloat16", one_chip),
            _spec((N_PAGES, PAGE, HKV, D), "int8", one_chip),
            _spec((N_PAGES, PAGE, HKV, D), "int8", one_chip),
            _spec((N_PAGES, HKV), "float32", one_chip),
            _spec((N_PAGES, HKV), "float32", one_chip),
            _spec((B, PPS), "int32", one_chip),
            _spec((B,), "int32", one_chip))
    _assert_kernel(
        paged_attention_quant.lower(*args, interpret=False).compile())


def test_quantize_pages_compiles(one_chip):
    pages = _spec((N_PAGES, PAGE, HKV, D), "bfloat16", one_chip)
    _assert_kernel(quantize_pages.lower(pages, interpret=False).compile())


def test_dequantize_pages_compiles(one_chip):
    q = _spec((N_PAGES, PAGE, HKV, D), "int8", one_chip)
    s = _spec((N_PAGES, HKV), "float32", one_chip)
    _assert_kernel(dequantize_pages.lower(
        q, s, out_dtype=jnp.bfloat16, interpret=False).compile())


def test_flash_attention_compiles(one_chip):
    q = _spec((1, HQ, PROMPT, D), "bfloat16", one_chip)
    kv = _spec((1, HKV, PROMPT, D), "bfloat16", one_chip)
    _assert_kernel(flash_attention.lower(q, kv, kv, causal=True,
                                         interpret=False).compile())
