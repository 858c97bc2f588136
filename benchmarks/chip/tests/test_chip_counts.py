"""The work counts the rooflines and utilisations divide by, and the table
of peaks they divide by."""
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import peaks  # noqa: E402
import run as harness  # noqa: E402
from systems import lm_serve, ntx_program  # noqa: E402


def _config(name):
    return harness.load_json(CHIP / "configs" / f"{name}.json")


def test_gemm_relu_counts():
    m, k, n = 256, 25088, 4096                       # VGG-16 fc6, batch 256
    w = ntx_program.work(_config("vgg16-fc6")["program"])
    assert w["flops"] == 2 * m * k * n + m * n       # MACs, then the ReLU
    assert w["bytes"] == 4 * (m * k + k * n + m * n)  # x, w read; h written


def test_chain_counts():
    rows, n = 4, 1 << 24
    program = {"inputs": {"x": [n], "y": [n]},
               "ops": [["axpy", "z", 0.7, "x", "y"], ["relu", "z", "z"],
                       ["argmax", "i", "z"]],
               "repeat": rows}
    w = ntx_program.work(program)
    # x, y read and z written per row, one index stored per row
    assert w["bytes"] == 4 * rows * (3 * n + 1)
    assert w["flops"] == rows * n * (2 + 1 + 1)      # AXPY, RELU, ARGMAX


def test_served_weights_per_token_match_the_program():
    from repro.configs.shapes import count_params
    config = _config("qwen2.5-7b-L14")
    cfg = lm_serve.arch(config)
    L, d = config["num_hidden_layers"], config["hidden_size"]
    vp = cfg.padded_vocab
    norms = (2 * L + 1) * d
    biases = L * (d + 2 * config["num_key_value_heads"] * cfg.hd)
    assert (L * lm_serve.layer_matmul_params(config) + 2 * vp * d + norms
            + biases == count_params(cfg))
    assert cfg.padded_vocab == lm_serve.lm_weights.padded_vocab(config)


def test_serve_work_by_hand():
    config = {"num_hidden_layers": 2, "hidden_size": 8,
              "intermediate_size": 16, "num_attention_heads": 2,
              "num_key_value_heads": 1, "vocab_size": 10}
    layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert lm_serve.layer_matmul_params(config) == layer
    w = lm_serve.work(config, {"batch": 3, "prompt_len": 5, "new_tokens": 2})
    per_tok, pair = 2 * 2 * layer, 4 * 2 * 2 * 4
    prefill = 3 * (5 * per_tok + pair * 15 + 2 * 8 * 10)   # 1+2+..+5 pairs
    decode = 3 * (per_tok + pair * 6 + 2 * 8 * 10)         # one step, kv 6
    assert w == {"flops": prefill + decode, "decode_steps": 1, "tokens": 6}


def test_peaks_table():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup("TPU v99")
    assert peaks.least_time_s(197e12, 0, v5e) == 1.0
    assert peaks.least_time_s(0, 819e9, v5e) == 1.0
