"""The controls (the reference in int8 and in fp8, put in the program's
place) fail the tiny cell's limit, on three seeds; the reference itself
reads 0.

At the cells' own sizes the same comparison runs on the chip
(``calibrate.py``), and PERF.md gives those readings beside each limit.
"""

import numpy as np
import pytest

import reference
from conftest import LLAMA, TINY_CONFIG, TINY_LIMIT


def rows(seed, n=4, prompt=128, served=64):
    rng = np.random.default_rng(seed)
    v = TINY_CONFIG["vocab_size"]
    return [(rng.integers(0, v, prompt).astype(np.int32),
             rng.integers(0, v, served).astype(np.int32)) for _ in range(n)]


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 1, 77])
@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_control_fails_the_limit(mode, seed):
    g = reference.gaps(LLAMA.logits, TINY_CONFIG, seed, rows(seed), [mode])
    assert g[mode]["max"] > TINY_LIMIT, g


def test_reference_reads_no_gap_on_its_own_tokens():
    """Served tokens that are the reference's own first choices read 0."""
    seed = 5
    (prompt, _), = rows(seed, n=1)
    seq = prompt
    for _ in range(8):          # greedy continuation by the reference
        (lg,) = LLAMA.logits(TINY_CONFIG, seed, [seq], [[len(seq) - 1]])
        seq = np.append(seq, np.int32(np.argmax(np.asarray(lg)[0])))
    g = reference.gaps(LLAMA.logits, TINY_CONFIG, seed,
                       [(prompt, seq[len(prompt):])])
    assert g["program"]["max"] == 0.0 and g["tokens"] == 8


def test_calibrate_reads_program_and_controls(tiny_root, capsys):
    """``calibrate.py`` on the tiny cell: one line a seed, then a summary
    per number; the program reads under the limit."""
    import json

    import calibrate
    rc = calibrate.main(["--workload", "tiny-cell", "--seeds", "2"],
                        root=tiny_root, require_tpu=False)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert rc == 0 and len(lines) == 2 + 3
    assert all(r["program"]["max"] <= TINY_LIMIT for r in lines[:2])
    # the verdict a run would give: the program correct, fp8 not
    assert all(r["correct"]["program"] and not r["correct"]["fp8"]
               for r in lines[:2])
    assert [s["number"] for s in lines[2:]] == ["max", "mean", "off"]
