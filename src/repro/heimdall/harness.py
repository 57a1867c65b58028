"""HEIMDALL harness: low-noise timing + tier placement helpers + CSV rows.

The paper runs its microbenchmarks in kernel space with prefetchers off; the
JAX analogue is jit-compiled closures timed over many repetitions with
explicit dispatch barriers (block_until_ready), warmup iterations discarded,
and median-of-runs reporting.
"""

from __future__ import annotations

import dataclasses
import functools
import statistics
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str                 # free-form derived metric, e.g. "GiB/s=12.3"
    n_reruns: int = 0            # noise-guard reruns behind this number

    def csv(self) -> str:
        # reruns ride inside the derived field: the CSV stays 3 columns,
        # so every existing consumer's name,us,derived split keeps working
        derived = self.derived if not self.n_reruns \
            else f"{self.derived};n_reruns={self.n_reruns}"
        return f"{self.name},{self.us_per_call:.3f},{derived}"


@dataclasses.dataclass(frozen=True)
class Timing:
    """One timed measurement with its noise signature.

    ``dispersion`` (IQR/median) is the noise guard the calibration fitter
    keys on: a sample whose repetitions scatter widely carries little
    information about the link constant and gets down-weighted (or rerun)
    instead of silently fitted.
    """
    median: float                # seconds per call
    iqr: float                   # interquartile range of the repetitions
    times: tuple                 # raw per-iteration seconds
    n_reruns: int = 0            # noise-guard retries taken (0 = first try)

    @property
    def dispersion(self) -> float:
        """IQR/median — scale-free instability measure (0 = perfectly
        repeatable; >~0.1 means the median is dominated by scheduler or
        allocator noise)."""
        return self.iqr / self.median if self.median > 0 else float("inf")


def time_fn_stats(fn: Callable, *args, warmup: int = 3, iters: int = 10,
                  inner: int = 1,
                  max_dispersion: Optional[float] = None,
                  max_reruns: int = 2) -> Timing:
    """Like ``time_fn`` but returns the full ``Timing`` (median + IQR
    dispersion) so callers can judge measurement stability.

    With ``max_dispersion`` set, a measurement whose dispersion exceeds it
    is remeasured (up to ``max_reruns`` times) and the *stablest* run wins
    — the same noise guard CalibrationRunner applies to link probes, now
    available to every benchmark family. ``Timing.n_reruns`` records how
    many retries stand behind the number (0 = clean first measurement),
    and ``Row`` surfaces it in the CSV so a noisy CI host is visible in
    the artifact rather than laundered into a plausible-looking median.
    """
    def _measure() -> Timing:
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            for _ in range(inner):
                out = fn(*args)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / inner)
        med = statistics.median(times)
        if len(times) >= 2:
            q = statistics.quantiles(times, n=4, method="inclusive")
            iqr = q[2] - q[0]
        else:
            iqr = 0.0
        return Timing(med, iqr, tuple(times))

    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    best = _measure()
    if max_dispersion is None:
        return best
    reruns = 0
    while best.dispersion > max_dispersion and reruns < max_reruns:
        reruns += 1
        t = _measure()
        if t.dispersion < best.dispersion:
            best = t
    return dataclasses.replace(best, n_reruns=reruns)


def time_fn(fn: Callable, *args, warmup: int = 3, iters: int = 10,
            inner: int = 1) -> float:
    """Median wall-time per call in seconds."""
    return time_fn_stats(fn, *args, warmup=warmup, iters=iters,
                         inner=inner).median


@functools.cache
def backend_memory_kinds():
    """Memory kinds the default device addresses. Cached — called per
    array placement."""
    return frozenset(m.kind for m in jax.devices()[0].addressable_memories())


def supported_memory_kind(kind):
    """The requested memory kind where the device addresses it — the single
    policy shared by tier_sharding and core.offload. Only the CPU backend
    collapses a missing kind into default memory (None); on an accelerator
    an unaddressable kind raises rather than silently moving a tier."""
    kinds = backend_memory_kinds()
    if kind in kinds:
        return kind
    platform = jax.devices()[0].platform
    if platform == "cpu":
        return None
    raise ValueError(f"memory kind {kind!r} is not addressable on "
                     f"{platform}: it has {sorted(kinds)}")


def tier_sharding(memory_kind: str = "device",
                  mesh=None) -> NamedSharding:
    """Sharding pinned to a memory tier.

    On a CPU backend without the kind, all tiers collapse into the default
    memory (relative tier numbers compress, as micro.py's header notes);
    elsewhere a missing kind raises.
    """
    if mesh is None:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("x",))
    return NamedSharding(mesh, P(),
                         memory_kind=supported_memory_kind(memory_kind))


_TIER_KINDS = {"hbm": "device", "device": "device",
               "host": "pinned_host", "pinned_host": "pinned_host"}


def place(x: jax.Array, tier: str) -> jax.Array:
    """tier: 'hbm' -> device memory, 'host' -> pinned_host."""
    if tier not in _TIER_KINDS:
        raise ValueError(
            f"unknown tier {tier!r}: JAX can only place arrays in "
            f"{sorted(set(_TIER_KINDS))}; simulated-only tiers (e.g. "
            f"'pool') live in repro.fabric system presets, not here")
    return jax.device_put(x, tier_sharding(_TIER_KINDS[tier]))


TIERS = ("hbm", "host")
