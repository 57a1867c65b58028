"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its phase
functions still work at tiny sizes (kernels in interpret mode), so the
script cannot rot between chip runs."""

import importlib.util
import json
import pathlib

import jax
import pytest

from repro.config.base import get_config

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_cpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok": true' not in out
    assert "needs a TPU" in err and "cpu" in err


def test_serve_phase_tiny(smoke):
    cfg = get_config("yi-9b").reduced(num_layers=2, num_kv_heads=2)
    out = smoke.serve_phase(cfg, jax.devices()[:1], batch=2, prompt=16,
                            gen=3)
    assert out["tokens"].shape == (2, 3)
    assert out["decode_err"]["max_over_rms"] <= smoke.TOL_MAX


def test_tiered_kv_phase_tiny(smoke):
    out = smoke.tiered_kv_phase(n_pages=32, page=8, kv_heads=2,
                                head_dim=16, q_heads=4, n_seqs=4,
                                min_bytes=0)
    assert set(out) == {"bf16", "int8"}
    # half the pages sit on the host tier; int8 pages move ~half the bytes
    assert out["bf16"]["host_pages"] == out["int8"]["host_pages"] == 16
    assert out["int8"]["bytes"] < 0.6 * out["bf16"]["bytes"]


def test_logit_check_rejects_low_precision(smoke):
    """The tolerance is tight enough that float8-level rounding fails it."""
    import numpy as np
    rng = np.random.default_rng(0)
    ref = rng.normal(size=(4, 512)).astype(np.float32)
    assert smoke.logit_error(ref, ref)["max_over_rms"] == 0.0
    coarse = np.asarray(jax.numpy.asarray(ref).astype(
        jax.numpy.float8_e4m3fn).astype(jax.numpy.float32))
    with pytest.raises(smoke.SmokeError):
        smoke.check_logits("fp8", coarse, ref)


def test_last_line_is_json_on_success(smoke, monkeypatch, capsys):
    """With the phases stubbed and the platform faked, main's last line is
    exactly the result object the contract names."""
    class Dev:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0
    monkeypatch.setattr(smoke.jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda c: "x")
    monkeypatch.setattr(smoke, "serve_phase", lambda *a, **k: {})
    monkeypatch.setattr(smoke, "tiered_kv_phase", lambda **k: {})
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
