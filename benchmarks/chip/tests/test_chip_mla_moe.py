"""The DeepSeek-V2 cell on the CPU at small widths: the float32 reference
against the program's prefill and cached decode (absorbed and expanded),
the chip's expert share against the uncut layer, a whole run with faults
injected, the grouped-GEMM roofline read from a synthetic trace, and the
work counts by hand."""
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import peaks  # noqa: E402
import run as harness  # noqa: E402
import trace_reduce as tr  # noqa: E402
from reference import mla_moe_f32, mla_moe_weights  # noqa: E402
from systems import mla_moe_serve  # noqa: E402

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "serve.mla_moe.chat")
PUBLISHED = harness.load_json(CHIP / "configs" / "deepseek-v2-lite-E8.json")
#: the cell's configuration at small widths: 16 routed experts of which
#: this chip holds 4 (4..7), top-6, YaRN as published
SMALL = dict(PUBLISHED, hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_rope_head_dim=16, qk_nope_head_dim=16, v_head_dim=16,
             moe_intermediate_size=32, n_routed_experts=4, expert_offset=4,
             published={"n_routed_experts": 16}, num_hidden_layers=3,
             vocab_size=300,
             serving={"prefill_microbatch": 2, "mla_absorb": True})


def small_traffic():
    return dict(harness.load_json(CHIP / "traffic" / "chat_b128.json"),
                batch=2, prompt_len=8, new_tokens=4, prompt_sets=1,
                check_requests=2)


def _run(config=SMALL, seed=2 ** 31 + 11):
    return harness.run_cell(BENCH, CELL, config, small_traffic(), seed,
                            seconds=0.0, trace=False)


def _program_logits(config, params, prompt, served, absorbed):
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    model = Model(mla_moe_serve.arch(config))
    logits, cache, fill = model.prefill(
        params, {"tokens": jnp.asarray(np.stack([prompt, prompt]))},
        cache_len=len(prompt) + len(served))
    out = [logits[0]]
    decode = jax.jit(model.decode, static_argnames=("absorbed_mla",))
    for t in served[:-1]:
        logits, cache = decode(params, jnp.asarray([[t], [t]], jnp.int32),
                               cache, jnp.int32(fill), absorbed_mla=absorbed)
        fill += 1
        out.append(logits[0, -1])
    v = config["vocab_size"]
    return np.stack([np.asarray(x[:v], np.float32) for x in out])


@pytest.mark.parametrize("absorbed", [True, False])
def test_reference_matches_prefill_and_cached_decode(absorbed):
    import jax
    key = jax.random.key(7)
    params = jax.jit(lambda k: mla_moe_weights.params(SMALL, k))(key)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, SMALL["vocab_size"], 12)
    served = rng.integers(0, SMALL["vocab_size"], 5)
    got = _program_logits(SMALL, params, prompt, served, absorbed)
    ref = np.asarray(mla_moe_f32.forward(SMALL, key, [(prompt, served)])[
        "ref"][0])
    assert got.shape == ref.shape == (5, SMALL["vocab_size"])
    rel = np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2))
    # bf16 weights and activations through 3 layers: ~1e-2 relative (a
    # routing flip near a tie moves one token by a gate's share); a wrong
    # rope, scale, cache slot or expert reads O(1)
    assert rel < 3e-2, rel
    assert np.mean(got.argmax(-1) == ref.argmax(-1)) >= 0.8


def test_yarn_frequencies_of_program_and_reference_agree():
    from repro.models.common import yarn_freqs
    cfg = mla_moe_serve.arch(PUBLISHED)
    np.testing.assert_allclose(
        yarn_freqs(64, cfg.rope_theta, cfg.yarn),
        mla_moe_f32.yarn_inv_freq(PUBLISHED), rtol=1e-6)
    from repro.models.attention import _mla_scale
    assert _mla_scale(cfg) == pytest.approx(mla_moe_f32.softmax_scale(
        PUBLISHED))


def test_expert_shares_sum_to_the_uncut_layer():
    """Each of 4 chips holds 4 of the 16 experts: the program's MoE outputs
    of the 4 shares, with the shared experts counted once, add up to what
    the reference gives for the whole layer."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe
    base = dict(SMALL, torch_dtype="float32")
    key = jax.random.key(3)
    x = jax.random.normal(jax.random.key(4), (2, 8, base["hidden_size"]))
    uncut = dict(base, n_routed_experts=16, expert_offset=0)
    w = {k: v.astype(jnp.float32) for k, v in
         mla_moe_weights.layer(uncut, key, 1, True).items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(mla_moe_f32.moe_ffn(uncut, w, x.reshape(16, -1)))
        shared = np.asarray(mla_moe_f32._swiglu(
            x.reshape(16, -1), w["ffn/shared/w1"], w["ffn/shared/w2"],
            w["ffn/shared/w3"], False))
        total = -3 * shared
        for off in (0, 4, 8, 12):
            share = dict(base, expert_offset=off)
            cfg = mla_moe_serve.arch(share)
            lw = mla_moe_weights.layer(share, key, 1, True)
            p = {"router": lw["ffn/router"], "w1": lw["ffn/w1"],
                 "w2": lw["ffn/w2"], "w3": lw["ffn/w3"],
                 "shared": {k: lw[f"ffn/shared/{k}"]
                            for k in ("w1", "w2", "w3")}}
            y, _, _ = moe.apply_moe(cfg, p, x)
            total = total + np.asarray(y).reshape(16, -1)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_cell_is_correct_and_counts_the_held_experts():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] == 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}


def test_window_reports_the_counter_and_the_gmm_work():
    import jax
    st = mla_moe_serve.setup(SMALL, small_traffic(), jax.random.key(1),
                             harness._span)
    run = mla_moe_serve.window(st, 0.0, harness._span, None)
    moe_layers = SMALL["num_hidden_layers"] - 1
    # every token of the prefill and of each decode step routes top-6 of
    # 16: at most 4 of its 6 land on the held experts
    tokens = 2 * 8 + 4 * 2
    assert 0 < run["moe_assigned"] <= tokens * 4 * moe_layers
    assert run["gmm_work"] == mla_moe_serve.gmm_work(
        SMALL, run["moe_assigned"], 1 + 4)


def test_altered_token_is_caught(monkeypatch):
    import repro.runtime.serve as serve

    def worst_for_first(logits):        # request 0 gets its least likely id
        ids = np.asarray(logits).argmax(-1)
        ids[0] = np.asarray(logits)[0].argmin()
        return ids

    monkeypatch.setattr(serve, "greedy_argmax_multistream", worst_for_first)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["served_logit_gap"]["value"] > 1.0


def test_half_batch_left_out_is_caught(monkeypatch):
    from repro.runtime.serve import Server
    orig = Server.generate

    def half(self, prompts, extra=None):
        out = orig(self, prompts, extra)
        out["completions"] = out["completions"][:len(prompts) // 2]
        return out

    monkeypatch.setattr(Server, "generate", half)
    res = _run()
    assert not res["correct"] and res["failed"] >= 1


def test_unsupported_config_is_refused():
    """A configuration stating what the program does not compute."""
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        mla_moe_serve.arch(dict(PUBLISHED, routed_scaling_factor=2.5))


MS = 1_000_000  # ns


def test_moe_gmm_roofline_from_a_synthetic_trace():
    ops = [("%ragged-dot-metadata = (s32[9]) custom-call()", 0, 1 * MS),
           ("%ragged-dot-none.1 = bf16[768,1408] custom-call()", 1 * MS,
            3 * MS),
           ("%ragged-dot-none = bf16[768,2048] custom-call()", 3 * MS,
            4 * MS),
           ("%fusion.3 = bf16[768,2048] fusion()", 4 * MS, 9 * MS)]
    trace = tr.Trace({"/device:TPU:0": {"ops": ops, "modules": []}},
                     [(tr.WINDOW_SPAN, 0, 10 * MS)])
    reduced = tr.reduce(trace)
    peak = peaks.lookup("TPU v5 lite")
    work = {"flops": 0.0, "bytes": 819e9 * 1.5e-3}      # 1.5 ms of bytes
    metric = harness.load_metric("moe_gmm_roofline")
    value = metric.read({"trace": reduced, "run": {"gmm_work": work},
                         "peak": peak})
    assert value == pytest.approx(50.0)                 # over 3 ms of GEMM
    assert metric.read({"trace": reduced, "run": {}, "peak": peak}) is None
    bare = tr.reduce(tr.Trace({"/device:TPU:0": {"ops": ops[3:],
                                                 "modules": []}},
                              [(tr.WINDOW_SPAN, 0, 10 * MS)]))
    assert metric.read({"trace": bare, "run": {"gmm_work": work},
                        "peak": peak}) is None


def test_work_by_hand():
    config = dict(SMALL, hidden_size=8, intermediate_size=16,
                  num_attention_heads=2, kv_lora_rank=4, qk_rope_head_dim=2,
                  qk_nope_head_dim=4, v_head_dim=4, moe_intermediate_size=6,
                  n_routed_experts=2, num_experts_per_tok=3,
                  published={"n_routed_experts": 8}, n_shared_experts=1,
                  num_hidden_layers=3, first_k_dense_replace=1,
                  vocab_size=10)
    d, h, r, dr, dn, dv, ffe = 8, 2, 4, 2, 4, 4, 6
    proj = 2 * (d * h * (dn + dr) + d * (r + dr) + h * dv * d)
    dense = 2 * 3 * d * 16
    moe = 2 * (d * 8 + 3 * d * ffe) + (3 * 2 / 8) * 6 * d * ffe
    per_tok = 3 * proj + dense + 2 * moe
    w = mla_moe_serve.work(config, {"batch": 3, "prompt_len": 5,
                                    "new_tokens": 2})
    expand = 2 * r * h * (dn + dv)
    pair = 2 * h * (dn + dr + dv)
    prefill = 3 * (5 * (per_tok + 3 * expand) + 3 * pair * 15 + 2 * d * 10)
    key = 2 * h * (2 * r + dr)
    absorb = 2 * h * dn * r + 2 * h * r * dv
    decode = 3 * (per_tok + 3 * (absorb + key * 6) + 2 * d * 10)
    assert w == {"flops": pytest.approx(prefill + decode),
                 "decode_steps": 1, "tokens": 6}
    g = mla_moe_serve.gmm_work(dict(config, torch_dtype="bfloat16"), 100, 7)
    assert g == {"flops": 6 * d * ffe * 100,
                 "bytes": 2 * 3 * d * ffe * 2 * 2 * 7 + 100 * 2 * d * 2}


def test_served_weights_match_the_program():
    from repro.configs.shapes import count_params
    cfg = mla_moe_serve.arch(PUBLISHED)
    import jax
    from repro.models import Model
    tree = jax.eval_shape(lambda: mla_moe_weights.params(
        PUBLISHED, jax.random.key(0)))
    want = jax.eval_shape(Model(cfg).init, 0)
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    n = count_params(cfg)
    assert 3.05e9 < n < 3.15e9          # 6.22 GB of bf16
    assert cfg.n_experts == 64 and cfg.n_held_experts == 8
    assert cfg.d_ff == 10944 and cfg.d_ff_expert == 1408


def test_control_fails_and_program_passes():
    # 8 layers, so that float8's error compounds as it does over the
    # cell's 27; the limit is the cell's own
    import controls_mla_moe
    config = dict(SMALL, num_hidden_layers=8, vocab_size=512)
    traffic = dict(small_traffic(), batch=4, prompt_len=16, new_tokens=16,
                   check_requests=4)
    r = controls_mla_moe.mla_moe_readings(config, traffic, seed=5)
    limit = traffic["checks"]["served_logit_gap"]
    assert r["malformed"] == 0
    assert r["program"]["served_logit_gap"] <= limit, r
    assert r["control"]["served_logit_gap"] > limit, r
