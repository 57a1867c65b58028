"""The peak table is keyed by device kind; anything else is refused."""

import pytest

import harness as H
import run_cell


def test_v5e_peaks_and_source():
    p = H.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16 * 2 ** 30
    assert "TPU v5e" in H.read_json(H.HERE / "peaks.json")["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(H.BenchError, match="no peaks"):
        H.peaks(kind)


@pytest.mark.parametrize("cell", ["yi24-decode", "yi24-prefill"])
def test_no_tpu_exits_nonzero_and_prints_no_result(cell, capsys):
    rc = run_cell.main(["--workload", cell, "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 1
    assert out.out == ""
    assert "needs" in out.err


def test_unknown_cell_exits_nonzero(capsys):
    rc = run_cell.main(["--workload", "nope", "--seed", "1",
                        "--seconds", "1"])
    assert rc == 2 and capsys.readouterr().out == ""
