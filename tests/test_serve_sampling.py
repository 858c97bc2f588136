"""Batched temperature sampling as a descriptor program (runtime/serve.py).

The sampling prep chain — scale-by-temperature AXPY (+ Gumbel noise) ->
optional THRESH prune -> ARGMAX chain-reduce tail — must fuse into one
pass per request, execute request-per-cluster on the mesh, and agree with
``jax.nn.softmax`` sampling both exactly (shared noise, Gumbel-max
identity) and in distribution.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.stream import FusedChainReduce, plan_stream
from repro.runtime.serve import (ServeConfig, Server,
                                 _TEMPERATURE_PROGRAMS,
                                 temperature_sample_multistream)

RNG = np.random.default_rng(42)


def _logits(b, vocab, scale=3.0):
    return (RNG.standard_normal((b, vocab)) * scale).astype(np.float32)


def _gumbel(shape):
    return RNG.gumbel(size=shape).astype(np.float32)


def test_matches_jax_softmax_sampling_exactly():
    """Gumbel-max identity: argmax(log softmax(z/T) + g) is an exact
    softmax(z/T) draw AND equals argmax(z/T + g) — the descriptor
    program must reproduce the jax.nn.softmax-based sampler bit-for-bit
    given the same noise."""
    b, vocab, T = 5, 96, 0.8
    logits = _logits(b, vocab)
    g = _gumbel((b, vocab))
    tok = temperature_sample_multistream(logits, T, g)
    log_p = np.log(np.asarray(
        jax.nn.softmax(jnp.asarray(logits) / T, axis=-1)))
    ref = np.argmax(log_p + g, axis=-1)
    np.testing.assert_array_equal(tok, ref)


def test_empirical_distribution_tracks_softmax():
    b, vocab, T = 8, 6, 1.3
    logits = _logits(b, vocab, scale=1.0)
    p_ref = np.asarray(jax.nn.softmax(jnp.asarray(logits) / T, axis=-1))
    counts = np.zeros((b, vocab))
    n_draws = 600
    gs = RNG.gumbel(size=(n_draws, b, vocab)).astype(np.float32)
    for i in range(n_draws):
        toks = temperature_sample_multistream(logits, T, gs[i])
        counts[np.arange(b), toks] += 1
    emp = counts / n_draws
    np.testing.assert_allclose(emp, p_ref, atol=0.08)


def test_sampling_chain_fuses_and_runs_on_the_mesh():
    b, vocab, T = 4, 64, 0.5
    logits = _logits(b, vocab)
    temperature_sample_multistream(logits, T, _gumbel((b, vocab)))
    prog, executor, *_ = _TEMPERATURE_PROGRAMS[(b, vocab, T, None)]
    groups = plan_stream(prog.descriptors)
    # one fused AXPY -> ARGMAX chain-reduce per request
    assert len(groups) == b
    assert all(isinstance(g, FusedChainReduce) for g in groups)
    assert all(g.red_op == "argmax" for g in groups)
    assert executor.stats["policy"] == "multistream"
    assert executor.stats["scheduler"]["n_substreams"] == b


def test_min_logit_threshold_prunes():
    """The THRESH stage: tokens whose perturbed scaled logit falls at or
    below the floor drop out of the lottery — the winner is the argmax
    over the *survivors*, never a pruned token."""
    b, vocab, T = 3, 32, 1.0
    logits = _logits(b, vocab)
    g = _gumbel((b, vocab))
    z = logits / T + g
    floor = float(np.quantile(z, 0.6))
    tok = temperature_sample_multistream(logits, T, g, min_logit=floor)
    survivors = np.where(z > floor, z, -np.inf)
    np.testing.assert_array_equal(tok, np.argmax(survivors, axis=-1))
    # a floor above every perturbed logit leaves all-zero rows -> index 0
    tok0 = temperature_sample_multistream(logits, T, g, min_logit=500.0)
    assert (tok0 == 0).all()
    # the THRESH variant caches separately and fuses the 3-stage chain
    ent = _TEMPERATURE_PROGRAMS[(b, vocab, T, floor)]
    groups = plan_stream(ent[0].descriptors)
    assert all(isinstance(gr, FusedChainReduce) and len(gr.descs) == 3
               for gr in groups)


def test_min_logit_all_negative_survivors():
    """Regression: with every perturbed logit negative, a pruned token
    must not out-rank the surviving one (THRESH zeroes prunes, so the
    chain runs positively shifted)."""
    logits = np.full((1, 8), -10.0, np.float32)
    logits[0, 3] = -2.0
    g = np.zeros((1, 8), np.float32)
    tok = temperature_sample_multistream(logits, 1.0, g, min_logit=-5.0)
    assert tok[0] == 3


def test_temperature_zero_rejected():
    with pytest.raises(ValueError):
        temperature_sample_multistream(_logits(1, 8), 0.0, _gumbel((1, 8)))


def test_server_sample_routes_temperature_through_program():
    """ServeConfig.temperature > 0 with multistream routes _sample
    through the descriptor program (host only draws the noise)."""
    srv = object.__new__(Server)                 # no model needed
    srv.scfg = ServeConfig(temperature=0.9)
    rng = np.random.default_rng(0)
    logits = _logits(6, 40)
    toks = srv._sample(jnp.asarray(logits), rng)
    assert toks.shape == (6,)
    assert ((0 <= toks) & (toks < 40)).all()
    # reproducible: same seed, same draw
    toks2 = srv._sample(jnp.asarray(logits), np.random.default_rng(0))
    np.testing.assert_array_equal(toks, toks2)
    # greedy path unchanged
    srv.scfg = ServeConfig(temperature=0.0)
    greedy = srv._sample(jnp.asarray(logits), rng)
    np.testing.assert_array_equal(greedy, logits.argmax(-1))
