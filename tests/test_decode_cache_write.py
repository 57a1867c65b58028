"""The decode step writes only the new token's K/V rows, in place.

Compiles ``Model.decode`` with the cache donated, as ``ServeEngine`` runs
it, and reads the optimized HLO: every cache leaf is aliased input to
output, and no instruction yields a value of a whole attention cache
leaf's shape except the row writes after the layer scan: no zero-filled
second cache, no copy, no whole-layer update inside the loop. float32,
since the CPU backend computes bfloat16 in float32 and would convert the
cache to do so, where the chip reads it as it is.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.config.base import ParallelConfig, get_config
from repro.launch.mesh import make_host_mesh
from repro.models.decode import cache_specs
from repro.models.model import Model

B, S = 2, 40

HEAD = re.compile(r"^(ENTRY )?%(\S+) \(.*\{$")
INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)"
                   r"\(([^)]*)\)(.*)$")
CALLS = re.compile(r"calls=%([\w.\-]+)")
ALIAS = re.compile(r"\{(\d+)\}: \((\d+), \{\}")


def _computations(hlo: str):
    """{computation: {instruction: (type, opcode, operands, rest)}}, the
    entry computation's name and the output -> parameter aliases."""
    comps, entry, name = {}, None, None
    for line in hlo.splitlines():
        if head := HEAD.match(line):
            name = head.group(2)
            comps[name] = {}
            if head.group(1):
                entry = name
        elif (ins := INSTR.match(line)) and name is not None:
            ops = [o.strip().split(" ")[-1].lstrip("%")
                   for o in re.sub(r"/\*[^*]*\*/", "", ins[4]).split(",")]
            comps[name][ins[1]] = (ins[2], ins[3], ops, ins[5])
    header = hlo.split("\n", 1)[0]
    aliases = {(int(o), int(p)) for o, p in ALIAS.findall(
        header[header.index("input_output_alias"):])}
    return comps, entry, aliases


def _hlo_type(shape, dtype) -> str:
    return f"{jnp.dtype(dtype).name.replace('float', 'f')}[" \
           f"{','.join(map(str, shape))}]"


@pytest.mark.parametrize("arch", ["yi-9b", "mixtral-8x22b", "gemma3-27b",
                                  "zamba2-7b", "qwen2-72b"])
def test_decode_writes_rows_in_place(arch):
    cfg = get_config(arch).reduced(dtype="float32")
    m = Model.create(cfg, make_host_mesh(), ParallelConfig(remat="none"))
    params = jax.eval_shape(lambda: m.init(jax.random.key(0)))
    specs = cache_specs(cfg, m.mctx, B, S)
    cache = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)), specs,
        is_leaf=lambda x: not isinstance(x, dict))
    hlo = jax.jit(m.decode, donate_argnums=(1,)).lower(
        params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    comps, entry, aliases = _computations(hlo)

    # every cache leaf: output 1 + i (after the logits) is parameter P + i
    n_params, n_cache = len(jax.tree.leaves(params)), len(jax.tree.leaves(
        cache))
    assert aliases == {(1 + i, n_params + i) for i in range(n_cache)}

    kv = [c for path, c in jax.tree_util.tree_leaves_with_path(cache)
          if path[-1].key in ("k", "v") and c.size]   # not empty segments
    whole = {_hlo_type(c.shape, c.dtype) for c in kv}
    rows = {_hlo_type(c.shape[:-3] + (1,) + c.shape[-2:], c.dtype)
            for c in kv}
    called = {CALLS.search(rest)[1] for _, op, _, rest in
              comps[entry].values() if op == "fusion"}

    def row_write(comp, ins):
        """A dynamic-update-slice of a row, outside the layer loop."""
        _, op, ops, _ = ins
        return (op == "dynamic-update-slice" and comp in called | {entry}
                and comps[comp][ops[1]][0] in rows)

    writes, faults = 0, []
    for comp, instrs in comps.items():
        for name, ins in instrs.items():
            ty, op, _, rest = ins
            if ty not in whole or op in ("parameter", "get-tuple-element",
                                         "bitcast"):
                continue
            if comp == entry and op == "fusion":
                fused = CALLS.search(rest)[1]
                if any(row_write(fused, i) for i in comps[fused].values()):
                    writes += 1
                    continue
            elif row_write(comp, ins):
                writes += comp == entry
                continue
            faults.append(f"{comp}: %{name} = {ty} {op}")
    assert not faults, faults
    assert writes == len(kv)
