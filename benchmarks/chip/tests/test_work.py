"""Operation and byte counts of the llama architecture file: the
program's parameter count, and one count worked by hand."""

import pytest

import harness as H
import system  # noqa: F401  (puts the program on the path)
from conftest import LLAMA


@pytest.mark.parametrize("config", ["yi-9b-24L", "yi-9b-tp4"])
def test_params_match_the_program(config):
    from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
    from repro.models.model import Model
    conf = H.read_json(H.HERE / "configs" / f"{config}.json")
    mesh = make_mesh((1, 1), (DATA_AXIS, MODEL_AXIS))
    model = Model.create(LLAMA.program_config(conf), mesh)
    assert LLAMA.params(LLAMA.sizes(conf)) == model.num_params


def test_yi_9b_whole_is_about_8_8_billion():
    conf = H.read_json(H.HERE / "configs" / "yi-9b-tp4.json")
    assert LLAMA.params(LLAMA.sizes(conf)) == 8_829_407_232


# d=4, f=8, 2 query heads and 1 kv head of 2, vocab 10, one layer
S = {"d": 4, "f": 8, "h": 2, "kv": 1, "dh": 2, "v": 10, "layers": 1}


def test_hand_worked_counts():
    # q,k,v: 4*(2+1+1)*2 = 32; o: 2*2*4 = 16; gate, up, down: 3*4*8 = 96
    assert LLAMA.layer_matrix_params(S) == 144
    # embedding and output 2*10*4, final norm 4, layer 144 + two norms 8
    assert LLAMA.params(S) == 236
    # 3 prompt tokens: 3 * 2*144 = 864; causal attention sees 1+2+3 = 6
    # keys, 4 * 2 heads * 2 * 6 = 96; last token's logits 2*4*10 = 80
    assert LLAMA.prefill_flops(S, [3]) == 1040
    # one token with 5 keys in view: 2*144 + 80 + 4*2*2*5
    assert LLAMA.decode_flops(S, [5]) == 448
    # k and v of 5 positions: 2 * 1 head * 2 * 2 bytes * 5
    assert LLAMA.kv_bytes(S, 5) == 40
    # matrices and output (144 + 40) * 2, kv 40, norms 3*4*2, one row 4*2
    assert LLAMA.decode_step_bytes(S, [5]) == 440
    assert LLAMA.decode_step_bytes(S, [5], chips=2) == (368 + 40) / 2 + 32
    assert LLAMA.flash_flops(S, 1, 3) == 96
    # q, k, v, out of 3 tokens: 3 * 2 * 2 bytes * (2*2 + 2*1)
    assert LLAMA.flash_bytes(S, 1, 3) == 72


def test_decode_attention_counts_live_context_only():
    a = LLAMA.decode_flops(S, [100])
    b = LLAMA.decode_flops(S, [101])
    assert b - a == 4 * S["h"] * S["dh"] * S["layers"]
