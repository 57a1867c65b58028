"""Finds a cell's files by name and turns a run's record into metrics.

Everything about one configuration, architecture, traffic mix, per-layer
metric or cell limit is a file of its own, found from ``BENCHMARK.json``
by name:

- ``configs/<config>.json``: the file a configuration entry names;
- ``architectures/<name>.py``: everything that knows a layer's shape, for
  each configuration whose ``architectures[0]`` is ``name`` (as in a
  published ``config.json``);
- ``traffic/<traffic>.json``: parameters for ``traffic.schedule``;
- ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``
  (``None``: nothing to read in this run, and the metric is left out)
  and, optionally, ``describe(run) -> str`` printed beside the value;
- ``limits/<cell>.json``: the limit on each number ``correct`` compares;
- ``peaks.json``: the chip's peaks, keyed by ``device_kind``.

So a new cell, mix, metric or architecture is new files and new entries,
not new code.

An architecture file provides:

- ``sizes(config)``: the widths, hashable (``weights.Sizes``);
- ``program_config(config)``: the program's ``ModelConfig``, and
  ``program_params(model, sizes, key)``: the seed's weights in the
  program's tree and shardings, made on the devices in one jitted call
  (``weights.on_devices``), each leaf drawn by ``weights.draw``;
- ``logits(config, seed, seqs, positions, mode, device)``: the plain
  float32 reference at ``HIGHEST`` precision, its weights drawn layer by
  layer, every matrix product through ``reference.matmul(x, w, mode)`` so
  that ``mode`` ``"int8"`` or ``"fp8"`` gives the controls;
- the counts the readers use: ``params(s)``, ``prefill_flops(s,
  prompt_lens)``, ``decode_flops(s, contexts)``, ``decode_step_bytes(s,
  contexts, chips)``, ``flash_flops(s, batch, t)`` and ``flash_bytes(s,
  batch, t)``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


class BenchError(Exception):
    """The benchmark cannot run this cell as asked."""


def read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"{path} does not exist") from None


@dataclasses.dataclass
class Cell:
    name: str
    paths: str            # the benchmark's directory, from the root
    chips: int
    config: dict
    arch: object          # the configuration's architecture file
    traffic: dict
    limits: dict
    end_to_end: list      # BENCHMARK.json entries that this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; there "
                         f"are {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = root / bench["paths"][0]
    config = read_json(root / conf["file"])
    if not config.get("architectures"):
        raise BenchError(f"{conf['file']} names no architecture (its "
                         f"'architectures' key)")
    return Cell(
        name=name, paths=bench["paths"][0], chips=w["chips"], config=config,
        arch=architecture(config["architectures"][0], here),
        traffic=read_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(here / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def peaks(device_kind: str, root: Path = HERE) -> dict:
    table = read_json(root / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (it has {sorted(table['devices'])})")
    return table["devices"][device_kind]


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = HERE):
    path = root / "metrics" / f"{metric}.py"
    if not path.exists():
        raise BenchError(f"metric {metric!r} has no reader at {path}")
    return _module(path, f"metric_{metric.replace('.', '_')}")


def architecture(name: str, root: Path = HERE):
    path = root / "architectures" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"architecture {name!r} has no file at {path}")
    return _module(path, f"arch_{name}")


@dataclasses.dataclass
class Batch:
    """One served batch as the window saw it."""
    prompts: list          # int32 arrays
    max_new: list          # per request
    served: list           # per request: the first token, then decode's
    t_submit: float
    t_first: float
    t_done: float

    @property
    def size(self) -> int:
        return len(self.prompts)

    @property
    def padded_len(self) -> int:
        return max(len(p) for p in self.prompts)

    @property
    def steps(self) -> int:
        return max(self.max_new)


@dataclasses.dataclass
class Run:
    """What a per-layer reader gets."""
    cell: Cell
    sizes: dict            # the architecture's sizes of the configuration
    peak: dict             # peaks.json entry of this chip
    batches: list          # [Batch]
    trace: object = None   # trace_reduce.Trace, in a --trace 1 run

    @property
    def chips(self) -> int:
        return self.cell.chips


def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, float), 95))


def end_to_end(batches: list, window_s: float) -> dict:
    """gen_tok_s, ttft_p95_ms, tpot_p95_ms and the sample counts."""
    ttft, tpot = [], []
    for b in batches:
        for m in b.max_new:
            ttft.append((b.t_first - b.t_submit) * 1e3)
            tpot.append((b.t_done - b.t_first) * 1e3 / m)
    return {"gen_tok_s": sum(sum(b.max_new) for b in batches) / window_s,
            "ttft_p95_ms": p95(ttft), "tpot_p95_ms": p95(tpot),
            "samples": len(ttft)}


def check_sample(batches: list, n: int, seed: int) -> list:
    """(prompt, served) of ``n`` finished requests drawn from the seed, the
    longest (prompt and output) among them."""
    rows = [(p, np.asarray(s, np.int32))
            for b in batches for p, s in zip(b.prompts, b.served)]
    longest = max(range(len(rows)),
                  key=lambda i: len(rows[i][0]) + len(rows[i][1]))
    rest = [i for i in range(len(rows)) if i != longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(rest, size=min(n - 1, len(rest)), replace=False)
    return [rows[longest]] + [rows[i] for i in sorted(pick)]


# each limit in ``limits/<cell>.json`` and the statistic of
# ``reference.gaps`` it holds
GAP_NUMBERS = {"max_gap": "max", "mean_gap": "mean"}


def judge(stats: dict, failed: int, limits: dict) -> tuple[bool, dict]:
    """``correct`` and every number compared, each beside its limit, for
    the gap statistics ``stats`` of one sequence of served tokens."""
    checks = {k: {"value": stats[GAP_NUMBERS[k]], "limit": v["limit"]}
              for k, v in limits.items()}
    checks["malformed"] = {"value": failed, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def malformed(b: Batch, vocab: int) -> int:
    """Requests of a batch whose output is not max_new + 1 token ids."""
    return sum(len(s) != m + 1 or not all(0 <= t < vocab for t in s)
               for s, m in zip(b.served, b.max_new))
