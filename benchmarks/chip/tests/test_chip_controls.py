"""The controls of ``correct`` at CPU sizes: the plain reference computed
one precision below the configuration's fails the cell's check, and the
program passes it. ``controls.py`` reads the same numbers on the chip at
the cells' own sizes."""
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "tests"))

import controls  # noqa: E402
from test_chip_harness import small_lm, small_ntx  # noqa: E402


@pytest.mark.parametrize("mix", ["gemm_relu", "chain_argmax"])
def test_ntx_control_fails_and_program_passes(mix):
    config, traffic = small_ntx(mix)
    r = controls.ntx_readings(config, traffic, seed=5)
    limits = {k: v["limit"] for k, v in config["checks"].items()}
    assert all(r["program"][k] <= limits[k] for k in limits), r
    assert any(r["control"][k] > limits[k] for k in limits), r


def test_serve_control_fails_and_program_passes():
    # deep enough (16 layers, as the cell) for float8's error to compound
    config, traffic = small_lm()
    config.update(num_hidden_layers=16, vocab_size=512)
    traffic.update(batch=4, prompt_len=16, new_tokens=16, check_requests=4)
    r = controls.serve_readings(config, traffic, seed=5)
    limit = traffic["checks"]["served_logit_gap"]
    assert r["malformed"] == 0
    assert r["program"]["served_logit_gap"] <= limit, r
    assert r["control"]["served_logit_gap"] > limit, r
