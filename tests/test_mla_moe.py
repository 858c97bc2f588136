"""DeepSeek-V2's mechanisms in the model code: YaRN-scaled rope and softmax
scale (against the published formula, written out here), the leading
dense layer, the dropless MoE over the held experts (against a dense
per-token sum), the one-pass absorbed MLA decode kernel (in interpret
mode, against the jnp form), and the server's jitted decode (absorbed
MLA, donated cache, MoE counters)."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.kernels import mla_decode
from repro.models import Model, attention, common, moe
from repro.models.attention import _mla_scale
from repro.models.transformer import decode_onepass_layers, layer_schedule
from repro.runtime import ServeConfig, Server

FULL = configs.get("deepseek-v2-lite-16b")


def _published_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse frequencies,
    as published (float64)."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def test_yarn_inverse_frequencies_match_the_published_formula():
    y = FULL.yarn
    got = common.yarn_freqs(FULL.rope_head_dim, FULL.rope_theta, y)
    want = _published_yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(common.rope_freqs(64, 1e4))
    changed = ~np.isclose(got, plain, rtol=1e-6)
    assert changed.sum() == 21 and not changed[:11].any()   # pairs 11..31


def test_yarn_softmax_scale_matches_the_published_formula():
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert _mla_scale(FULL) == pytest.approx(192 ** -0.5 * mscale * mscale)
    assert mscale * mscale == pytest.approx(1.590, abs=1e-3)
    # mscale == mscale_all_dim: cos and sin keep their magnitude
    x = jnp.ones((1, 1, 3, 64), jnp.float32)
    pos = jnp.arange(3)[None]
    out = common.apply_rope(x, pos, 1e4, FULL.yarn)
    np.testing.assert_allclose(
        np.asarray(out[0, 0, 0]), np.ones(64), rtol=1e-6)   # position 0


def test_plain_rope_is_bit_identical():
    """Without YaRN the rope is the formula every other config ran."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 4, 7, 128)), jnp.bfloat16)
    pos = jnp.asarray(rng.integers(0, 4000, (2, 7)), jnp.int32)
    theta = 1e6
    freqs = 1.0 / (theta ** (jnp.arange(0, 128, 2, dtype=jnp.float32) / 128))
    ang = pos[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    want = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)
    got = common.apply_rope(x, pos, theta)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("cfg", [FULL, configs.get_reduced(
    "deepseek-v2-lite-16b")], ids=["published", "reduced"])
def test_leading_dense_layer_in_the_schedule(cfg):
    sched, kinds, idx = layer_schedule(cfg)
    assert sched[0] == "attn_mlp"
    assert set(sched[1:]) == {"attn_moe"}
    assert kinds == ["attn_mlp", "attn_moe"]
    assert idx[:3] == [0, 0, 1]
    assert cfg.yarn is not None and not cfg.norm_topk_prob


# ----------------------------------------------------------------------
# MoE: dense per-token reference
# ----------------------------------------------------------------------
def _moe_cfg(**kw):
    base = configs.get_reduced("deepseek-v2-lite-16b").scaled(
        compute_dtype="float32", param_dtype="float32")
    return base.scaled(**kw)


def _dense_moe(cfg, p, x, offset=0, held=None):
    """Per token: softmax over all experts, greedy top-k, the held experts'
    SwiGLU outputs weighted by their gates, plus the shared experts."""
    held = cfg.n_experts if held is None else held
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = x @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    silu = lambda z: z / (1 + np.exp(-z))
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:cfg.top_k]
        g = probs[t, top]
        if cfg.norm_topk_prob:
            g = g / g.sum()
        for e, w in zip(top, g):
            j = e - offset
            if 0 <= j < held:
                w1, w2, w3 = (np.asarray(p[k][j], np.float64)
                              for k in ("w1", "w2", "w3"))
                out[t] += w * (silu(x[t] @ w1) * (x[t] @ w3)) @ w2
    sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
    out += (silu(x @ sh["w1"]) * (x @ sh["w3"])) @ sh["w2"]
    return out


def test_all_tokens_to_one_expert_drop_nothing():
    """Every token of a batch routes to expert 3 (and 5): a capacity-
    buffered dispatch would drop most of them; the dropless layer matches
    the dense per-token sum."""
    cfg = _moe_cfg()
    p = moe.moe_params(cfg, jax.random.key(0))
    router = np.zeros((cfg.d_model, cfg.n_experts), np.float32)
    router[:, 3], router[:, 5] = 4.0, 2.0
    p["router"] = jnp.asarray(router)
    rng = np.random.default_rng(1)
    x = jnp.asarray(np.abs(rng.standard_normal((3, 16, cfg.d_model))) * 0.1,
                    jnp.float32)
    y, _, load = moe.apply_moe(cfg, p, x)
    assert np.asarray(load)[3] == np.asarray(load)[5] == 3 * 16
    assert np.asarray(load).sum() == 3 * 16 * cfg.top_k
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model),
                               _dense_moe(cfg, p, x), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("norm", [False, True])
def test_norm_topk_prob_on_and_off(norm):
    cfg = _moe_cfg(norm_topk_prob=norm)
    p = moe.moe_params(cfg, jax.random.key(2))
    x = jax.random.normal(jax.random.key(3), (2, 8, cfg.d_model))
    y, _, _ = moe.apply_moe(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model),
                               _dense_moe(cfg, p, x), rtol=1e-4, atol=1e-5)
    other = cfg.scaled(norm_topk_prob=not norm)
    y2, _, _ = moe.apply_moe(other, p, x)
    assert not np.allclose(np.asarray(y), np.asarray(y2), atol=1e-3)


def test_held_share_is_the_held_experts_part():
    """A chip holding experts 2..4 of 8 computes their part of the routed
    sum (plus the shared experts) and counts only their assignments."""
    full = _moe_cfg()
    p = moe.moe_params(full, jax.random.key(4))
    x = jax.random.normal(jax.random.key(5), (2, 8, full.d_model))
    cfg = full.scaled(expert_offset=2, experts_held=3)
    share = dict(p, **{k: p[k][2:5] for k in ("w1", "w2", "w3")})
    y, _, load = moe.apply_moe(cfg, share, x)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model),
                               _dense_moe(cfg, share, x, 2, 3),
                               rtol=1e-4, atol=1e-5)
    _, _, load_all = moe.apply_moe(full, p, x)
    np.testing.assert_array_equal(np.asarray(load),
                                  np.asarray(load_all)[2:5])


# ----------------------------------------------------------------------
# Cached decode and the server
# ----------------------------------------------------------------------
@pytest.mark.parametrize("absorbed", [False, True])
def test_decode_matches_prefill_continuation(absorbed):
    cfg = _moe_cfg()
    model = Model(cfg)
    params = model.init(0)
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (2, 17))
    full, _, _ = model.prefill(params, {"tokens": jnp.asarray(toks)},
                               cache_len=24)
    _, cache, fill = model.prefill(params, {"tokens": jnp.asarray(
        toks[:, :16])}, cache_len=24)
    step, cache = model.decode(params, jnp.asarray(toks[:, 16:]), cache,
                               jnp.int32(fill), absorbed_mla=absorbed)
    # the cache is stored in bf16 while the direct forward attends in f32
    np.testing.assert_allclose(np.asarray(full), np.asarray(step[:, 0]),
                               rtol=2e-2, atol=2e-2)


def _onepass_cfg():
    """The reduced config with a latent of one 128-lane row, the
    narrowest the one-pass kernel takes."""
    return configs.get_reduced("deepseek-v2-lite-16b").scaled(
        kv_lora_rank=128)


BK = mla_decode.BLOCK_K


@pytest.mark.parametrize("fill", [1, BK - 1, BK, BK + 1, 2 * BK - 1])
def test_onepass_kernel_matches_the_jnp_absorbed_form(fill, monkeypatch):
    """Layer 2 of a 3-layer stack of 16 rows (two blocks of rows) and two
    blocks of positions: the kernel's form of ``_mla_attend_absorbed``,
    taken as it is lowered for a TPU but in interpret mode, against the
    jnp form it takes on the CPU."""
    cfg = _onepass_cfg()
    n_layers, b, seq, layer = 3, 16, 2 * BK, 2
    ks = jax.random.split(jax.random.key(fill), 4)
    p = attention.mla_params(cfg, ks[0])
    x = jax.random.normal(ks[1], (b, 1, cfg.d_model), cfg.cdtype)
    pos = jnp.full((b, 1), fill)
    q_nope, q_rope, c_new, k_new = attention._mla_qkv(cfg, p, x, pos)
    new = {"c_kv": c_new, "k_rope": k_new}
    stack = {"c_kv": jax.random.normal(
        ks[2], (n_layers, b, seq, cfg.kv_lora_rank), jnp.bfloat16),
        "k_rope": jax.random.normal(
            ks[3], (n_layers, b, 1, seq, cfg.rope_head_dim), jnp.bfloat16)}
    args = (cfg, p, q_nope, q_rope, stack, new, jnp.int32(fill),
            jnp.int32(layer))
    want = attention._mla_attend_absorbed(*args)      # lowered for the CPU
    assert attention.mla_onepass_fits(cfg, b, 1, seq)
    onepass = attention._latent_onepass
    monkeypatch.setattr(attention, "_latent_onepass",
                        lambda *a: onepass(*a, interpret=True))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda tpu, default: tpu())
    got = attention._mla_attend_absorbed(*args)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    # the jnp form rounds normalised weights to bf16, the kernel the
    # running ones: a bf16 ulp of the output apart at most
    np.testing.assert_allclose(got, want, rtol=2e-2,
                               atol=2e-2 * np.abs(want).max())


def test_onepass_layers_counts_attention_layers_lowered_for_a_tpu():
    cfg = _onepass_cfg()
    assert decode_onepass_layers(cfg, 8, 2 * BK, "tpu", True) == \
        cfg.n_layers
    assert decode_onepass_layers(cfg, 8, 2 * BK, "cpu", True) == 0
    assert decode_onepass_layers(cfg, 8, 2 * BK, "tpu", False) == 0
    assert decode_onepass_layers(cfg, 8, BK + 8, "tpu", True) == 0
    # at a latent narrower than 128 lanes the TPU lays the stack out so
    # that the kernel would copy it: not taken
    small = configs.get_reduced("deepseek-v2-lite-16b")
    assert decode_onepass_layers(small, 8, 2 * BK, "tpu", True) == 0


def test_server_decode_is_jit_decode_absorbed_and_donated():
    cfg = configs.get_reduced("deepseek-v2-lite-16b")
    model = Model(cfg)
    params = model.init(0)
    srv = Server(cfg, params, ServeConfig(max_seq=16, max_new_tokens=3,
                                          eos_token=-1))
    cache = model.init_cache(2, 16)
    text = srv._decode.lower(params, jnp.zeros((2,), jnp.int32), cache,
                             jnp.int32(4),
                             absorbed_mla=cfg.mla_absorb).as_text(
                                 debug_info=True)
    assert "module @jit_decode " in text
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
    assert "mla.attend/bhsr,bkr->bhsk" in text   # the absorbed form's scores
    assert "stablehlo.case" not in text          # no branch over layer kinds


def test_generate_counts_the_held_experts_assignments():
    cfg = configs.get_reduced("deepseek-v2-lite-16b")
    srv = Server(cfg, Model(cfg).init(0),
                 ServeConfig(max_seq=16, max_new_tokens=3, eos_token=-1))
    b, s, new = 2, 6, 3
    out = srv.generate([np.arange(s) + 7 * r for r in range(b)])
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    # all experts held: every assignment of the prefill and of the
    # ``new - 1`` decode steps is counted (the last token needs no forward)
    assert out["moe_assigned"] == (b * s + (new - 1) * b) * cfg.top_k \
        * moe_layers
    assert out["moe_assigned"] / cfg.n_experts <= out[
        "moe_max_expert_load"] <= out["moe_assigned"]
    dense = configs.get_reduced("llama3-8b")
    out = Server(dense, Model(dense).init(0), ServeConfig(
        max_seq=16, max_new_tokens=2, eos_token=-1)).generate([np.arange(4)])
    assert set(out) == {"completions", "prefill_s", "decode_tok_per_s",
                        "steps_ahead", "steps_discarded"}


def test_generate_span_carries_the_moe_counts(tmp_path):
    import glob
    import os
    cfg = configs.get_reduced("deepseek-v2-lite-16b")
    srv = Server(cfg, Model(cfg).init(0),
                 ServeConfig(max_seq=16, max_new_tokens=2, eos_token=-1))
    prompts = [np.arange(5), np.arange(5) + 3]
    srv.generate(prompts)                           # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = srv.generate(prompts)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    (stats,) = [dict(e.stats) for plane in data.planes
                if plane.name.startswith("/host:") for line in plane.lines
                for e in line.events if e.name == "serve.generate"]
    assert stats["moe_assigned"] == out["moe_assigned"] > 0
    assert stats["moe_max_expert_load"] == out["moe_max_expert_load"]
