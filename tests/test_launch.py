"""Launch plumbing: compile-cache location, the serve CLI's --reduced flag,
parameter init (deterministic across processes, created in its own dtype
and sharding), and the memory-kind policy."""

import hashlib
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import get_config
from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_wins(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


@pytest.mark.parametrize("argv,reduced", [([], False),
                                          (["--reduced"], True),
                                          (["--no-reduced"], False)])
def test_serve_reduced_flag(monkeypatch, argv, reduced):
    """--reduced is off by default (published widths) and can be turned
    on and off; the flag is read before any engine is built."""
    from repro.launch import serve
    seen = {}

    class Stop(Exception):
        pass

    def fake_engine(cfg, **kw):
        seen["d_model"] = cfg.d_model
        raise Stop
    monkeypatch.setattr(serve, "ServeEngine", fake_engine)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["serve", *argv])
    with pytest.raises(Stop):
        serve.main()
    full = get_config("yi-9b").d_model
    assert (seen["d_model"] != full) == reduced


_DIGEST = """
import hashlib, jax, numpy as np
from repro.config.base import get_config
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
m = Model.create(get_config("yi-9b").reduced(), make_host_mesh())
h = hashlib.sha256()
for leaf in jax.tree.leaves(m.init(jax.random.key(7))):
    h.update(np.asarray(leaf).tobytes())
print(h.hexdigest())
"""


def test_init_is_deterministic_across_processes():
    """One seed, two processes with different string-hash salts: the same
    parameters (path keys use a stable hash, not Python's hash)."""
    digests = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, JAX_PLATFORMS="cpu",
                   PYTHONPATH=str(ROOT / "src"))
        out = subprocess.run([sys.executable, "-c", _DIGEST], env=env,
                             capture_output=True, text=True, timeout=300,
                             check=True)
        digests.append(out.stdout.strip().splitlines()[-1])
    assert digests[0] == digests[1]


def test_init_in_dtype_and_sharding(host_mesh):
    """init(dtype=bf16) draws each leaf in bf16 with its spec's sharding,
    and equals the float32 init cast to bf16 (the same draw)."""
    from repro.models.model import Model
    m = Model.create(get_config("yi-9b").reduced(), host_mesh)
    bf = m.init(jax.random.key(3), dtype=jnp.bfloat16)
    f32 = m.init(jax.random.key(3))
    want = m.abstract_params(dtype=jnp.bfloat16)
    for a, w, f in zip(jax.tree.leaves(bf), jax.tree.leaves(want),
                       jax.tree.leaves(f32)):
        assert a.dtype == jnp.bfloat16 and a.shape == w.shape
        assert a.sharding.is_equivalent_to(w.sharding, a.ndim)
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(f.astype(jnp.bfloat16)))


def test_put_tree_keeps_devices_and_moves_memory():
    from repro.core.offload import put_tree
    x = jax.device_put(jnp.ones((4, 4)), jax.devices()[0])
    host = put_tree({"x": x}, "pinned_host")["x"]
    assert host.sharding.memory_kind == "pinned_host"
    assert host.sharding.device_set == x.sharding.device_set
    back = put_tree({"x": host}, "device")["x"]
    assert back.sharding.memory_kind == "device"
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_memory_kind_collapses_only_on_cpu(monkeypatch):
    from repro.heimdall import harness
    monkeypatch.setattr(harness, "backend_memory_kinds",
                        lambda: frozenset({"device"}))
    assert harness.supported_memory_kind("device") == "device"
    assert harness.supported_memory_kind("pinned_host") is None   # CPU

    class Tpu:
        platform = "tpu"
    monkeypatch.setattr(harness.jax, "devices", lambda: [Tpu()])
    with pytest.raises(ValueError, match="pinned_host"):
        harness.supported_memory_kind("pinned_host")
