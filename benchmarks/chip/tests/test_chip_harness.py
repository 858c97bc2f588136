"""A whole run of each kind of cell on the CPU at small sizes, past the
harness's look for a chip: ``correct`` holds for the program as it is,
and comes out false when the timed path is broken underneath — an answer
or a token altered where it is produced, half of a batch left out."""
import copy
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import run as harness  # noqa: E402

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
#: the descriptor-program cell, ready as data but not in BENCHMARK.json
#: until the program keeps its Pallas GEMM compiled between calls
NTX_CELL = {"name": "ntx.gemm", "config": "vgg16-fc6",
            "traffic": "back_to_back", "chips": 1}
BENCH_NTX = dict(
    BENCH, workloads=BENCH["workloads"] + [NTX_CELL],
    end_to_end=BENCH["end_to_end"] + [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["ntx.gemm"]}
        for name in ("program_ms", "program_p95_ms")])
CELLS = {w["name"]: w for w in BENCH_NTX["workloads"]}


def _config(name):
    return harness.load_json(CHIP / "configs" / f"{name}.json")


def _traffic(name):
    return harness.load_json(CHIP / "traffic" / f"{name}.json")


#: a memory-bound streaming program (AXPY -> RELU with an ARGMAX tail,
#: twice over) beside the cell's GEMM, with the limits it read on the chip
CHAIN = {"program": {"inputs": {"x": [4096], "y": [4096]},
                     "ops": [["axpy", "z", 0.7, "x", "y"], ["relu", "z", "z"],
                             ["argmax", "i", "z"]],
                     "repeat": 2},
         "checks": {"z": {"compare": "max_err_over_bound", "limit": 1e-06},
                    "i": {"compare": "mismatches", "limit": 0}}}


def small_ntx(mix: str):
    """The cell's configuration at CPU sizes (every dimension cut to 128
    at most) or, for ``chain_argmax``, the streaming program; kernels in
    Pallas interpret mode, the cell's own traffic."""
    config = dict(_config("vgg16-fc6"), backend="pallas_interpret")
    if mix == "chain_argmax":
        config.update(copy.deepcopy(CHAIN))
    else:
        program = copy.deepcopy(config["program"])
        for name, shape in program["inputs"].items():
            program["inputs"][name] = [min(s, 128) for s in shape]
        config["program"] = program
    return config, _traffic(NTX_CELL["traffic"])


def small_lm():
    """Qwen2's layout at small widths; the limit is the cell's own."""
    config = dict(_config("qwen2.5-7b-L14"), hidden_size=64,
                  intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, num_hidden_layers=2, vocab_size=300)
    traffic = dict(_traffic("chat_batch"), batch=2, prompt_len=8,
                   new_tokens=4, prompt_sets=1, check_requests=2)
    return config, traffic


def _run(cell, config, traffic, seed=3):
    """One run as ``cell`` reports it (the mix comes with ``traffic``)."""
    return harness.run_cell(BENCH_NTX, CELLS[cell], config, traffic, seed,
                            seconds=0.0, trace=False)


@pytest.mark.parametrize("mix", ["gemm_relu", "chain_argmax"])
def test_ntx_cell_is_correct(mix):
    res = _run("ntx.gemm", *small_ntx(mix))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"program_ms", "program_p95_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("mix,out", [("gemm_relu", "h"),
                                     ("chain_argmax", "i.1")])
def test_ntx_altered_answer_is_caught(monkeypatch, mix, out):
    from repro.core import executor as ex_mod
    from repro.core.program import ProgramResult
    orig = ex_mod.Executor.run

    def altered(self, program, inputs=None, policy=None):
        res = orig(self, program, inputs, policy)
        lo, _ = program.resolve(out).span
        return ProgramResult(program, res.mem.at[lo].add(1.0))

    monkeypatch.setattr(ex_mod.Executor, "run", altered)
    res = _run("ntx.gemm", *small_ntx(mix))
    assert not res["correct"] and res["failed"] >= 1


def test_serve_cell_is_correct():
    res = _run("serve.decode", *small_lm())
    assert res["correct"], res["checks"]
    assert res["attempted"] == 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}


def test_serve_altered_token_is_caught(monkeypatch):
    import repro.runtime.serve as serve

    def worst_for_first(logits):        # request 0 gets its least likely id
        ids = np.asarray(logits).argmax(-1)
        ids[0] = np.asarray(logits)[0].argmin()
        return ids

    monkeypatch.setattr(serve, "greedy_argmax_multistream", worst_for_first)
    res = _run("serve.decode", *small_lm())
    assert not res["correct"]
    assert res["checks"]["served_logit_gap"]["value"] > 1.0


def test_serve_half_batch_left_out_is_caught(monkeypatch):
    from repro.runtime.serve import Server
    orig = Server.generate

    def half(self, prompts, extra=None):
        out = orig(self, prompts, extra)
        out["completions"] = out["completions"][:len(prompts) // 2]
        return out

    monkeypatch.setattr(Server, "generate", half)
    res = _run("serve.decode", *small_lm())
    assert not res["correct"] and res["failed"] >= 1


def test_cell_metrics_follow_the_benchmark_file():
    names = [m["name"] for m in harness.cell_metrics(BENCH, "serve.decode",
                                                     True)]
    assert names == ["decode_step_ms", "device_idle.serve", "mfu.serve"]
    names = [m["name"] for m in harness.cell_metrics(BENCH, "serve.decode",
                                                     False)]
    assert names == ["serve_tok_s", "setup_s"]
    for m in BENCH["per_layer"]:          # every metric has its reader
        assert hasattr(harness.load_metric(m["name"]), "read")
        assert m["workloads"]             # and names the cells it reads


def test_large_seeds_are_accepted():
    key, rng = harness.derive_key(2 ** 31 + 12345)
    key2, _ = harness.derive_key(2 ** 31 + 12345)
    import jax
    assert (jax.random.key_data(key) == jax.random.key_data(key2)).all()
    assert 0 <= rng.integers(10) < 10
