"""The plain float32 reference agrees with the program's prefill and
cached decode at small Qwen2 widths, on the weights the benchmark
makes: the serve check compares against an independent forward."""
import sys
from pathlib import Path

import numpy as np

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import run as harness  # noqa: E402
from reference import lm_f32, lm_weights  # noqa: E402
from systems import lm_serve  # noqa: E402

CONFIG = dict(harness.load_json(CHIP / "configs" / "qwen2.5-7b-L14.json"),
              hidden_size=128, intermediate_size=256, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=3, vocab_size=500)


def _program_logits(params, prompt, served):
    """Prefill over the prompt, then cached decode of each served token
    but the last: the logits that chose each served token."""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    model = Model(lm_serve.arch(CONFIG))
    cache_len = len(prompt) + len(served)
    logits, cache, fill = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None], jnp.int32)},
        cache_len=cache_len)
    out = [logits[0]]
    decode = jax.jit(model.decode)
    for t in served[:-1]:
        logits, cache = decode(params, jnp.asarray([[t]], jnp.int32), cache,
                               jnp.int32(fill))
        fill += 1
        out.append(logits[0, -1])
    v = CONFIG["vocab_size"]
    return np.stack([np.asarray(x[:v], np.float32) for x in out])


def test_reference_matches_prefill_and_cached_decode():
    import jax
    key = jax.random.key(7)
    params = jax.jit(lambda k: lm_weights.params(CONFIG, k))(key)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CONFIG["vocab_size"], 12)
    served = rng.integers(0, CONFIG["vocab_size"], 5)
    got = _program_logits(params, prompt, served)
    ref = np.asarray(lm_f32.forward(CONFIG, key, [(prompt, served)])["ref"][0])
    assert got.shape == ref.shape == (5, CONFIG["vocab_size"])
    rel = np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2))
    # bf16 weights and activations through 3 layers: ~1e-2 relative; a
    # wrong position, head grouping or cache slot reads O(1)
    assert rel < 3e-2, rel
    assert np.mean(got.argmax(-1) == ref.argmax(-1)) >= 0.8


def test_greedy_served_tokens_read_near_zero_gap_and_others_do_not():
    import jax
    key = jax.random.key(3)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, CONFIG["vocab_size"], 10)
    served = rng.integers(0, CONFIG["vocab_size"], 4)
    ref = np.asarray(lm_f32.forward(CONFIG, key, [(prompt, served)])["ref"][0])
    gaps = lm_f32.served_gaps(CONFIG, key, [(prompt, served)])["served"][0]
    np.testing.assert_allclose(gaps, ref.max(-1) - ref[np.arange(4), served],
                               rtol=1e-6, atol=1e-6)
    # the reference's own greedy choice reads 0 at the first position
    first = np.array([ref[0].argmax()] + list(served[1:]))
    assert lm_f32.served_gaps(CONFIG, key, [(prompt, first)])[
        "served"][0][0] == 0.0
