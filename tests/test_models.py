"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
config of the same family and runs one forward/train step + decode on CPU,
asserting output shapes and finiteness (assignment deliverable f)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import ArchConfig, Model
from repro.runtime import ServeConfig, Server


def _batch(cfg, b=2, s=32, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                   jnp.int32)}
    if cfg.encoder_decoder:
        batch["enc_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.enc_seq, cfg.d_model)) * 0.02,
            jnp.bfloat16)
    if cfg.n_patches:
        batch["img_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.n_patches, cfg.d_model)) * 0.02,
            jnp.bfloat16)
        mask = np.ones((b, s), np.float32)
        mask[:, :cfg.n_patches] = 0
        batch["loss_mask"] = jnp.asarray(mask)
    return batch


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_reduced_smoke_train(arch):
    cfg = configs.get_reduced(arch)
    model = Model(cfg)
    params = model.init(0)
    batch = _batch(cfg)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss)), arch
    assert float(loss) > 0


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_reduced_smoke_decode(arch):
    cfg = configs.get_reduced(arch)
    model = Model(cfg)
    params = model.init(0)
    b, s = 2, 32
    batch = _batch(cfg, b, s)
    logits, cache, fill = model.prefill(params, batch, cache_len=s + 8)
    assert logits.shape == (b, cfg.padded_vocab)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache2 = jax.jit(model.decode)(params, tok, cache,
                                            jnp.int32(fill))
    assert logits2.shape == (b, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits2, np.float32)).all(), arch
    if cfg.moe:     # every assignment of the prefill and the step counted
        moe_layers = sum(map(cfg.is_moe_layer, range(cfg.n_layers)))
        assert int(cache2["moe_load"].sum()) == (
            b * (s + 1) * cfg.top_k * moe_layers), arch


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_full_config_shapes(arch):
    """FULL configs are exercised via eval_shape only (no allocation)."""
    cfg = configs.get(arch)
    n = configs.shapes.count_params(cfg)
    assert n > 0.5e9, (arch, n)  # all assigned archs are >= 0.8B params
    specs = configs.input_specs(cfg, "train_4k")
    assert specs["batch"]["tokens"].shape == (256, 4096)
    # decode specs include the cache pytree
    d = configs.input_specs(cfg, "decode_32k")
    assert d["tokens"].shape == (128, 1)
    leaves = jax.tree.leaves(d["cache"])
    assert leaves, arch


def test_shape_skips_recorded():
    ok, _ = configs.shape_applicable(configs.get("llama3-8b"), "long_500k")
    assert not ok
    ok, _ = configs.shape_applicable(configs.get("mamba2-1.3b"), "long_500k")
    assert ok
    ok, _ = configs.shape_applicable(configs.get("jamba-v0.1-52b"),
                                     "long_500k")
    assert ok


def test_decode_matches_prefill_continuation():
    """Decoding token s+1 from a prefilled cache must match prefilling
    s+1 tokens directly (cache correctness, dense arch)."""
    cfg = configs.get_reduced("llama3-8b").scaled(compute_dtype="float32",
                                                  param_dtype="float32")
    model = Model(cfg)
    params = model.init(0)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab, (1, 17)).astype(np.int32)
    b_full = {"tokens": jnp.asarray(toks),
              "labels": jnp.zeros_like(jnp.asarray(toks))}
    b_pre = {"tokens": jnp.asarray(toks[:, :16]),
             "labels": jnp.zeros((1, 16), jnp.int32)}
    logits_full, _, _ = model.prefill(params, b_full, cache_len=32)
    _, cache, fill = model.prefill(params, b_pre, cache_len=32)
    logits_step, _ = model.decode(params, jnp.asarray(toks[:, 16:17]),
                                  cache, jnp.int32(fill))
    # cache is stored bf16 (production layout) while the direct forward
    # attends in f32 -> small quantization differences are expected
    np.testing.assert_allclose(np.asarray(logits_full, np.float32),
                               np.asarray(logits_step[:, 0], np.float32),
                               rtol=2e-2, atol=2e-2)


def test_ssm_decode_matches_prefill_continuation():
    cfg = configs.get_reduced("mamba2-1.3b").scaled(compute_dtype="float32",
                                                    param_dtype="float32")
    model = Model(cfg)
    params = model.init(0)
    rng = np.random.default_rng(4)
    toks = rng.integers(0, cfg.vocab, (1, 17)).astype(np.int32)
    b_full = {"tokens": jnp.asarray(toks),
              "labels": jnp.zeros_like(jnp.asarray(toks))}
    b_pre = {"tokens": jnp.asarray(toks[:, :16]),
             "labels": jnp.zeros((1, 16), jnp.int32)}
    logits_full, _, _ = model.prefill(params, b_full, cache_len=32)
    _, cache, fill = model.prefill(params, b_pre, cache_len=32)
    logits_step, _ = model.decode(params, jnp.asarray(toks[:, 16:17]),
                                  cache, jnp.int32(fill))
    np.testing.assert_allclose(np.asarray(logits_full, np.float32),
                               np.asarray(logits_step[:, 0], np.float32),
                               rtol=2e-2, atol=2e-2)


def test_scan_unroll_parity():
    """The dry-run delta method's unrolled variant is numerically the
    production scan (exact at f32)."""
    cfg = configs.get_reduced("jamba-v0.1-52b").scaled(
        compute_dtype="float32", param_dtype="float32")
    m1 = Model(cfg)
    m2 = Model(cfg.scaled(unroll=True))
    params = m1.init(0)
    batch = _batch(cfg)
    l1, _ = m1.loss(params, batch)
    l2, _ = m2.loss(params, batch)
    assert abs(float(l1) - float(l2)) < 1e-4


def _qwen25_reduced():
    """Qwen2.5's decoder (GQA with query/key/value biases, one layer kind)
    at small widths, as ``benchmarks/chip/systems/lm_serve.arch`` builds
    it at published ones."""
    return ArchConfig(name="qwen2.5-reduced", family="dense", n_layers=3,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab=512, rope_theta=1e6, qkv_bias=True)


@pytest.mark.parametrize("arch,absorbed", [
    ("deepseek_v2_lite_16b", True),    # dense layer, then MoE layers
    ("deepseek_v2_lite_16b", False),
    ("jamba_v01_52b", False),          # interleaved period of 8
    ("qwen2.5", False),                # one kind
])
def test_scanned_decode_matches_unrolled(arch, absorbed):
    """Three decode steps after a prefill, scanned by segment and with every
    layer unrolled: same logits, equal caches, and each step changes the
    attention caches at its own position only. Without state-space layers
    the third step's logits continue the prefill of all the tokens."""
    cfg = (_qwen25_reduced() if arch == "qwen2.5"
           else configs.get_reduced(arch)).scaled(
        compute_dtype="float32", param_dtype="float32")
    model, unrolled = Model(cfg), Model(cfg.scaled(unroll=True))
    params = model.init(0)
    scanned = jax.jit(model.decode, static_argnames=("absorbed_mla",))
    flat = jax.jit(unrolled.decode, static_argnames=("absorbed_mla",))
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg.vocab, (2, 11)), jnp.int32)
    _, cache, fill = model.prefill(params, {"tokens": toks[:, :8]},
                                   cache_len=16)
    c_scan = c_flat = cache
    for t in range(3):
        tok, at = toks[:, 8 + t:9 + t], jnp.int32(fill + t)
        l_scan, n_scan = scanned(params, tok, c_scan, at,
                                 absorbed_mla=absorbed)
        l_flat, n_flat = flat(params, tok, c_flat, at, absorbed_mla=absorbed)
        np.testing.assert_allclose(np.asarray(l_scan), np.asarray(l_flat),
                                   rtol=2e-2, atol=2e-2)
        for (path, a), b, before in zip(
                jax.tree_util.tree_leaves_with_path(n_scan),
                jax.tree.leaves(n_flat), jax.tree.leaves(c_scan)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
            if path[0].key.startswith("attn"):    # (layers, ..., S, d)
                np.testing.assert_array_equal(
                    np.delete(np.asarray(a, np.float32), fill + t, -2),
                    np.delete(np.asarray(before, np.float32), fill + t, -2))
        c_scan, c_flat = n_scan, n_flat
    if cfg.ssm:     # jamba's cached decode departs from its prefill by up
        return      # to 0.24 here, scanned by segment or by layer switch
    full, _, _ = model.prefill(params, {"tokens": toks}, cache_len=16)
    np.testing.assert_allclose(np.asarray(full), np.asarray(l_scan[:, 0]),
                               rtol=2e-2, atol=2e-2)


def _no_branch_cases():
    """Every registered config's decode (ids as before: the arch), and the
    prefill of each decoder-only one."""
    return ([pytest.param("decode", a, id=a) for a in configs.ARCHS]
            + [pytest.param("prefill", a, id=f"{a}-prefill")
               for a in configs.ARCHS
               if not configs.get_reduced(a).encoder_decoder])


@pytest.mark.parametrize("phase,arch", _no_branch_cases())
def test_server_decode_takes_no_branch(phase, arch):
    """The served decode step is ``jit_decode`` with its cache donated and
    no conditional, and the jitted prefill has none either: a branch over
    layer kinds returns every kind's cache, which makes each layer copy
    the stacks it does not own."""
    cfg = configs.get_reduced(arch)
    model = Model(cfg)
    params = jax.eval_shape(lambda: model._mod.init_params(cfg, 0))
    if phase == "prefill":
        batch = jax.eval_shape(lambda: _batch(cfg))
        text = jax.jit(model.prefill, static_argnums=2).lower(
            params, batch, 40).as_text()
        assert "_prefill_chunked" in text
        assert "stablehlo.case" not in text
        return
    srv = Server(cfg, params, ServeConfig(max_seq=16, max_new_tokens=2,
                                          eos_token=-1))
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    text = srv._decode.lower(params, jnp.zeros((2,), jnp.int32), cache,
                             jnp.int32(4),
                             absorbed_mla=cfg.mla_absorb).as_text()
    assert "module @jit_decode " in text
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
    assert "stablehlo.case" not in text


@pytest.mark.parametrize("arch", [
    "deepseek_v2_lite_16b",            # dense layer, then MoE layers
    "jamba_v01_52b",                   # interleaved period of 8
    "qwen2.5",                         # one kind
])
def test_scanned_prefill_matches_unrolled(arch):
    """The prefill's walk of the layer segments, scanned and with every
    layer unrolled: the same last-position logits, and caches equal to
    one bf16 step (or 1e-5 near zero). The compiler fuses a scanned and an
    unrolled layer apart, so their float32 work can part in its last bits
    and a stored value round the other way."""
    cfg = (_qwen25_reduced() if arch == "qwen2.5"
           else configs.get_reduced(arch)).scaled(
        compute_dtype="float32", param_dtype="float32")
    model, unrolled = Model(cfg), Model(cfg.scaled(unroll=True))
    params = model.init(0)
    batch = {"tokens": jnp.asarray(np.random.default_rng(6).integers(
        0, cfg.vocab, (4, 12)), jnp.int32)}
    l_scan, c_scan, f_scan = model.prefill(params, batch, cache_len=16)
    l_flat, c_flat, f_flat = unrolled.prefill(params, batch, cache_len=16)
    assert f_scan == f_flat == 12
    np.testing.assert_allclose(np.asarray(l_scan), np.asarray(l_flat),
                               rtol=2e-2, atol=2e-2)
    assert jax.tree.structure(c_scan) == jax.tree.structure(c_flat)
    for a, b in zip(jax.tree.leaves(c_scan), jax.tree.leaves(c_flat)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2 ** -7, atol=1e-5)


def test_prefill_microbatch_not_dividing_runs_one_chunk(monkeypatch):
    """A ``prefill_microbatch`` that does not divide the batch prefills it
    as one chunk, the same program as ``prefill_microbatch=1``."""
    from repro.models import transformer
    cfg = configs.get_reduced("phi3.5-moe-42b-a6.6b").scaled(
        compute_dtype="float32", param_dtype="float32")
    chunked, whole = (Model(cfg.scaled(prefill_microbatch=4)),
                      Model(cfg.scaled(prefill_microbatch=1)))
    params = whole.init(0)
    batch = {"tokens": jnp.asarray(np.random.default_rng(7).integers(
        0, cfg.vocab, (6, 8)), jnp.int32)}
    chunks = []
    run = transformer._prefill_chunked

    def spy(cfg, params, batch, cache_len, mb):
        chunks.append(mb)
        return run(cfg, params, batch, cache_len, mb)

    monkeypatch.setattr(transformer, "_prefill_chunked", spy)
    l1, c1, f1 = chunked.prefill(params, batch, cache_len=12)
    l2, c2, f2 = whole.prefill(params, batch, cache_len=12)
    assert chunks == [1, 1] and f1 == f2 == 8
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
