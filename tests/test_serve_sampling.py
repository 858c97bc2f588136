"""Sampling in the serving loop (runtime/serve.py).

Greedy serving samples on the device and reads each step's ids one step
behind: its completions must equal a plain step-by-step decode with the
host's argmax, stop at EOS, and follow a numpy stand-in for the sampler.
Batched temperature sampling is a descriptor program: the sampling prep
chain — scale-by-temperature AXPY (+ Gumbel noise) ->
optional THRESH prune -> ARGMAX chain-reduce tail — must fuse into one
pass per request, execute request-per-cluster on the mesh, and agree with
``jax.nn.softmax`` sampling both exactly (shared noise, Gumbel-max
identity) and in distribution.
"""
import dataclasses
import functools
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.stream import FusedChainReduce, plan_stream
from repro.models import Model
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


# ----------------------------------------------------------------------
# Greedy serving: device ARGMAX, ids read one step behind
# ----------------------------------------------------------------------
def _reduced(arch):
    from repro import configs
    cfg = configs.get_reduced(arch)
    # a vocabulary short of its padding, so padded ids exist to be missed
    return dataclasses.replace(cfg, vocab=cfg.padded_vocab - 12)


SERVED = ["llama3-8b", "deepseek-v2-lite-16b"]     # dense GQA; MLA + MoE


@functools.lru_cache(maxsize=None)
def _served(arch):
    cfg = _reduced(arch)
    return cfg, Model(cfg).init(0)


def _prompts(b=3, s=6, vocab=500):
    return [(np.arange(s) * (r + 3) + 11 * r) % vocab for r in range(b)]


def _plain_greedy(cfg, params, prompts, new, max_seq=16):
    """``Model.decode`` step by step, each step's ids the host's
    ``np.argmax`` over the vocabulary: (b, new) ids."""
    model = Model(cfg)
    tokens = jnp.asarray(np.stack(prompts), jnp.int32)
    logits, cache, fill = model.prefill(
        params, {"tokens": tokens, "labels": jnp.zeros_like(tokens)},
        cache_len=max_seq)
    step = jax.jit(model.decode, static_argnames=("absorbed_mla",))
    cur = np.asarray(logits[:, :cfg.vocab], np.float32).argmax(-1)
    out = [cur]
    for i in range(new - 1):
        logits, cache = step(params, jnp.asarray(cur[:, None], jnp.int32),
                             cache, jnp.int32(fill + i),
                             absorbed_mla=cfg.mla_absorb)
        cur = np.asarray(logits[:, -1, :cfg.vocab], np.float32).argmax(-1)
        out.append(cur)
    return np.stack(out, 1)


def _serve(cfg, params, prompts, new, eos=-1):
    return Server(cfg, params, ServeConfig(max_seq=16, max_new_tokens=new,
                                           eos_token=eos)).generate(prompts)


@pytest.mark.parametrize("arch", SERVED)
def test_greedy_completions_equal_a_plain_decode_loop(arch):
    cfg, params = _served(arch)
    prompts, new = _prompts(vocab=cfg.vocab), 5
    out = _serve(cfg, params, prompts, new)
    np.testing.assert_array_equal(np.asarray(out["completions"]),
                                  _plain_greedy(cfg, params, prompts, new))
    assert (out["steps_ahead"], out["steps_discarded"]) == (new - 1, 0)


@pytest.mark.parametrize("arch", SERVED)
def test_eos_stops_each_row_and_the_call(arch):
    cfg, params = _served(arch)
    pool, new = _prompts(b=32, vocab=cfg.vocab), 8
    full = _serve(cfg, params, pool, new)["completions"]
    first = {}                          # token -> {row: step it first comes}
    for r, row in enumerate(full):
        for step, tok in enumerate(row):
            first.setdefault(tok, {}).setdefault(r, step)
    # an EOS two rows meet at different steps, both before the last
    eos, rows = next(
        (tok, (r0, r1)) for tok, at in sorted(first.items())
        for r0, r1 in itertools.combinations(at, 2)
        if at[r0] != at[r1] and max(at[r0], at[r1]) < new - 1)
    at = first[eos]
    other = next(r for r in range(len(pool)) if r not in at)
    # with a row that never meets EOS the call runs to its end; without
    # it, the call ends where the later row stops, dropping its queued step
    for picked, early in ((rows + (other,), False), (rows, True)):
        out = _serve(cfg, params, [pool[r] for r in picked], new, eos=eos)
        assert out["completions"] == [full[r][:at[r] + 1] if r in at
                                      else full[r] for r in picked]
        assert out["steps_discarded"] == int(early)
        assert out["steps_ahead"] == (max(at[r] for r in rows) + 1 if early
                                      else new - 1)


def test_numpy_stand_in_for_the_greedy_sampler_drives_the_loop(monkeypatch):
    """The seam the benchmark's tests use: a stand-in for
    ``greedy_argmax_multistream`` that returns numpy ids is looked up
    when the server samples, and its ids are served."""
    from repro.runtime import serve
    cfg, params = _served("llama3-8b")
    prompts, new = _prompts(vocab=cfg.vocab), 4
    want = _serve(cfg, params, prompts, new)["completions"]
    calls = []

    def host_argmax(logits):
        calls.append(np.shape(logits))
        return np.asarray(logits, np.float32).argmax(-1)

    monkeypatch.setattr(serve, "greedy_argmax_multistream", host_argmax)
    assert _serve(cfg, params, prompts, new)["completions"] == want
    assert calls == [(len(prompts), cfg.vocab)] * new

    def first_row_worst(logits):
        ids = np.asarray(logits, np.float32).argmax(-1)
        ids[0] = np.asarray(logits, np.float32)[0].argmin()
        return ids

    monkeypatch.setattr(serve, "greedy_argmax_multistream", first_row_worst)
    got = _serve(cfg, params, prompts, new)["completions"]
    assert got[0] != want[0] and got[1:] == want[1:]


def test_device_greedy_argmax_returns_device_int32_ids():
    from repro.runtime.serve import greedy_argmax_multistream
    logits = _logits(4, 300)
    ids = greedy_argmax_multistream(jnp.asarray(logits, jnp.bfloat16))
    assert isinstance(ids, jax.Array) and ids.dtype == jnp.int32
    np.testing.assert_array_equal(
        ids, np.asarray(jnp.asarray(logits, jnp.bfloat16),
                        np.float32).argmax(-1))
