"""A run with the timed path broken underneath must come out not correct.

Each test drives ``run_cell.main`` on a tiny cell on the CPU (the look for
a chip skipped) with one fault planted in the program, and reads the
result line: a decode step that returns its cache unchanged, half of each
batch left out (its rows given the other half's answers), one served token
altered where it is produced, and, on four virtual devices, the MLP's
all-reduce between chips left out. The same run without a fault is
correct.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import run_cell
import system
from conftest import HERE, TINY_CONFIG, write_cell

SEED = str(2 ** 40 + 11)


@pytest.fixture(autouse=True)
def no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def state_unchanged(mp):
    from repro.models.model import Model
    step = Model.decode
    mp.setattr(Model, "decode",
               lambda self, p, c, t, i: (step(self, p, c, t, i)[0], c))


def half_batch(mp):
    from repro.launch.serve import ServeEngine
    prefill = ServeEngine.prefill

    def first_half_twice(self, reqs):
        h = len(reqs) // 2
        return prefill(self, reqs[:h] + reqs[:h] + reqs[2 * h:])
    mp.setattr(ServeEngine, "prefill", first_half_twice)


def token_altered(mp):
    from repro.launch.serve import ServeEngine
    decode = ServeEngine.decode

    def altered(self, handoff, **kw):
        res = decode(self, handoff, **kw)
        for r in res:
            r.tokens[-1] = (r.tokens[-1] + 1) % self.cfg.vocab_size
        return res
    mp.setattr(ServeEngine, "decode", altered)


def result(root: Path, capsys) -> dict:
    rc = run_cell.main(["--workload", "tiny-cell", "--seed", SEED,
                        "--seconds", "1"], root=root, require_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [None, state_unchanged, half_batch,
                                   token_altered],
                         ids=["sound", "state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_comes_out_not_correct(fault, tiny_root, monkeypatch, capsys):
    if fault:
        fault(monkeypatch)
    res = result(tiny_root, capsys)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"


def no_allreduce(mp):
    """The MLP's down projection summed on each chip alone: the partial
    sums are never exchanged."""
    import jax
    from jax.sharding import PartitionSpec as P
    import repro.models.decode as dec
    import repro.models.transformer as tfm
    build = system.build_engine
    mesh = {}

    def build_and_keep_mesh(*a, **kw):
        engine = build(*a, **kw)
        mesh["m"] = engine.model.mctx.mesh
        return engine

    def mlp(p, x, gated=True, mctx=None):
        def local(x, wg, wu, wd):
            return (jax.nn.silu(x @ wg.astype(x.dtype))
                    * (x @ wu.astype(x.dtype))) @ wd.astype(x.dtype)
        col, row = P(None, "model"), P("model", None)
        return jax.shard_map(local, mesh=mesh["m"], in_specs=(P(), col, col,
                                                              row),
                             out_specs=P(), check_vma=False)(
            x, p["w_gate"], p["w_up"], p["w_down"])
    mp.setattr(system, "build_engine", build_and_keep_mesh)
    mp.setattr(dec, "mlp_apply", mlp)
    mp.setattr(tfm, "mlp_apply", mlp)


def tp_child(root: str, fault: str) -> None:
    """Run in a process with four virtual CPU devices."""
    mp = pytest.MonkeyPatch()
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    if fault == "no_allreduce":
        no_allreduce(mp)
    rc = run_cell.main(["--workload", "tiny-cell", "--seed", SEED,
                        "--seconds", "1"], root=Path(root), require_tpu=False)
    sys.exit(rc)


@pytest.mark.parametrize("fault", ["sound", "no_allreduce"])
def test_exchange_between_chips_left_out(fault, tmp_path):
    cfg = dict(TINY_CONFIG, num_attention_heads=8, num_key_value_heads=4,
               mesh={"data": 1, "model": 4})
    write_cell(tmp_path, config=cfg, chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (f"import sys; sys.path[:0] = [{str(HERE)!r}, "
            f"{str(HERE / 'tests')!r}]; import test_faults; "
            f"test_faults.tp_child({str(tmp_path)!r}, {fault!r})")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (fault == "sound"), res["checks"]
