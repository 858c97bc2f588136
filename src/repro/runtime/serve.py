"""Batched serving loop: prefill + decode with a pre-allocated KV cache.

Continuous-batching-lite: a fixed decode batch of slots; finished requests
(EOS or max-len) are replaced by queued requests whose prompts are
prefilled into the freed slot. Sampling uses the NTX ARGMAX command
(greedy) or temperature sampling. Works for all decoder archs, including
SSM/hybrid state caches.

Both samplers are descriptor :class:`~repro.core.program.Program`\\ s run
through the policy-driven :class:`~repro.core.executor.Executor`. Greedy:
each request's ARGMAX over its logits row is an independent sub-stream
(disjoint buffers), so the batch partitions request-per-cluster and
executes concurrently on the mesh — the serving-side use of the paper's
independent per-cluster streams. Temperature: sampling prep is the
streaming chain scale-by-temperature (AXPY ``logits/T + gumbel`` — the
Gumbel-max identity makes the added noise an exact draw from the softmax
distribution) -> optional THRESH prune -> ARGMAX chain-reduce tail, one
fused pass per request, regression-tested against ``jax.nn.softmax``
sampling. No hand-computed base addresses: the program's allocator owns
the layout.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import ExecutionPolicy, Executor, Program
from repro.core.spans import install_gc_span, span
from repro.models import ArchConfig, Model


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 512
    max_new_tokens: int = 32
    eos_token: int = 1
    temperature: float = 0.0
    seed: int = 0
    multistream: bool = True        # sampling programs via the cluster mesh
    pipeline: bool = True           # prefill sampling via the stage pipeline
    #: optional THRESH prune in the temperature-sampling chain: perturbed
    #: scaled logits at or below the floor drop to 0 before the ARGMAX
    #: tail (epsilon-style pruning in logit space; None disables the
    #: stage)
    min_logit: Optional[float] = None


#: (b, vocab) -> (Program, Executor, row handles, slot handles); the
#: Executor caches its plan (and jitted transports) on the Program, so
#: steady-state decode pays one dispatch per step.
_ARGMAX_PROGRAMS: Dict[tuple, Any] = {}
_PREFILL_PROGRAMS: Dict[tuple, Any] = {}
#: (b, vocab, temperature, min_logit) -> (Program, Executor, rows,
#: noise handles, slot handles) for the temperature-sampling chains
_TEMPERATURE_PROGRAMS: Dict[tuple, Any] = {}

#: positive bias applied (via the noise operand) when ``min_logit``
#: prunes: THRESH zeroes pruned entries, and the shift keeps every
#: *surviving* perturbed logit above 0 so a pruned token can never win
#: the ARGMAX. Power of two; assumes |logits/T + gumbel| < 1024.
_PRUNE_SHIFT = 1024.0


def _sampler_entry(cache: Dict[tuple, Any], b: int, vocab: int,
                   staged: bool, policy: str):
    ent = cache.get((b, vocab))
    if ent is None:
        prog = Program()
        rows, slots = [], []
        for i in range(b):
            row = prog.buffer((vocab,), name=f"row{i}")
            if staged:
                # COPY hands the head cluster's row off to the sampler
                # cluster (the inter-cluster DMA), ARGMAX reduces it
                row_staged = prog.copy(row)
                slots.append(prog.argmax(row_staged, name=f"slot{i}"))
            else:
                slots.append(prog.argmax(row, name=f"slot{i}"))
            rows.append(row)
        ent = (prog, Executor(ExecutionPolicy(policy=policy)), rows, slots)
        cache[(b, vocab)] = ent
    return ent


def _run_sampler(ent, logits) -> np.ndarray:
    prog, executor, rows, slots = ent
    res = executor.run(prog, inputs=dict(zip(rows, logits)))
    return _read_slots(res, slots)


def _read_slots(res, slots) -> np.ndarray:
    """The sampled ids, on the host: where the host waits for the
    device. Only the slots cross, one fp32 each (``bytes``), not the
    sampler's image."""
    with span("serve.readback", bytes=4 * len(slots)):
        return res.take(slots).astype(np.int64)


def greedy_argmax_multistream(logits) -> np.ndarray:
    """Greedy sampling as a multi-cluster descriptor program.

    One ARGMAX command per request row — independent uniform sub-streams
    the scheduler can stack (vmap/shard_map lanes), cached per batch
    shape. Ties resolve to the first maximum, matching ``np.argmax``.
    """
    logits = jnp.asarray(logits, jnp.float32)
    b, vocab = logits.shape
    return _run_sampler(
        _sampler_entry(_ARGMAX_PROGRAMS, b, vocab, staged=False,
                       policy="multistream"), logits)


def greedy_argmax_pipelined(logits) -> np.ndarray:
    """Prefill sampling as a stage-pipelined descriptor program.

    The LM head writes each request's logits row in its own (producer)
    cluster; the sampler consumes it in another. Per request the program
    is a dependent two-command chain: COPY streams the row into a staging
    buffer (the inter-cluster DMA handoff), then ARGMAX reduces the staged
    row to the token slot. ``StageSchedule`` level-izes the chains into a
    head stage and a sampler stage (uniform across requests, so they stack
    as vmap/shard_map lanes). Bit-equal to ``np.argmax`` (ties resolve to
    the first maximum).
    """
    logits = jnp.asarray(logits, jnp.float32)
    b, vocab = logits.shape
    return _run_sampler(
        _sampler_entry(_PREFILL_PROGRAMS, b, vocab, staged=True,
                       policy="pipeline"), logits)


def temperature_sample_multistream(logits, temperature: float, gumbel,
                                   min_logit: Optional[float] = None
                                   ) -> np.ndarray:
    """Batched temperature sampling as a descriptor program on the mesh.

    Per request the sampling prep is one fused streaming chain:
    scale-by-temperature (``AXPY``: ``logits/T + gumbel``) -> optional
    ``THRESH`` prune -> ``ARGMAX`` chain-reduce tail. By the Gumbel-max
    identity, ``argmax(logits/T + g)`` with i.i.d. standard Gumbel ``g``
    is an exact draw from ``softmax(logits/T)`` — so the ARGMAX tail (the
    comparator + index-counter datapath) IS the categorical sampler, no
    exp/normalise pass needed. Every request's chain is an independent
    uniform sub-stream, so the batch executes request-per-cluster
    (stacked vmap / shard_map lanes), exactly like greedy decode.

    ``gumbel`` is the (b, vocab) noise array — drawn by the caller so
    sampling stays reproducible and testable. With ``min_logit`` set, a
    THRESH stage prunes: tokens whose perturbed scaled logit is at or
    below the floor drop out of the lottery (epsilon-style pruning).
    Because THRESH zeroes rather than removes, the chain runs shifted by
    ``_PRUNE_SHIFT`` (folded into the noise operand, threshold shifted
    to match) so every surviving value stays positive and a pruned token
    can never out-rank a survivor; when *everything* is pruned the row
    is all zeros and the first index wins. The shift assumes
    ``|logits/T + gumbel| < 1024`` and may merge survivors closer than
    ~1e-4 (fp32 resolution at the shifted magnitude).
    """
    if temperature <= 0:
        raise ValueError(f"temperature must be > 0, got {temperature}")
    logits = jnp.asarray(logits, jnp.float32)
    b, vocab = logits.shape
    key = (b, vocab, float(temperature),
           None if min_logit is None else float(min_logit))
    ent = _TEMPERATURE_PROGRAMS.get(key)
    if ent is None:
        prog = Program()
        rows, noises, slots = [], [], []
        for i in range(b):
            row = prog.buffer((vocab,), name=f"row{i}")
            g = prog.buffer((vocab,), name=f"g{i}")
            z = prog.axpy(1.0 / temperature, row, g)
            if min_logit is not None:
                prog.thresh(z, min_logit + _PRUNE_SHIFT, out=z)
            slots.append(prog.argmax(z, name=f"slot{i}"))
            rows.append(row)
            noises.append(g)
        ent = (prog, Executor(ExecutionPolicy(policy="multistream")),
               rows, noises, slots)
        _TEMPERATURE_PROGRAMS[key] = ent
    prog, executor, rows, noises, slots = ent
    gumbel = jnp.asarray(gumbel, jnp.float32)
    if min_logit is not None:
        gumbel = gumbel + jnp.float32(_PRUNE_SHIFT)
    inputs: Dict[Any, Any] = dict(zip(rows, logits))
    inputs.update(zip(noises, gumbel))
    res = executor.run(prog, inputs=inputs)
    return _read_slots(res, slots)


#: the ``call`` stat of ``serve.generate`` spans: one id per call in the
#: process
_CALL_IDS = itertools.count()


class Server:
    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig):
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.model = Model(cfg)
        # the cache is donated: each step updates it in place
        self._decode = jax.jit(self.model.decode,
                               static_argnames=("absorbed_mla",),
                               donate_argnames=("cache",))
        install_gc_span()

    def _sample(self, logits: jnp.ndarray, rng,
                prefill: bool = False) -> np.ndarray:
        if self.scfg.temperature <= 0 and prefill and self.scfg.pipeline:
            # prefill: the logits row is handed off head-cluster ->
            # sampler-cluster through the stage pipeline
            return greedy_argmax_pipelined(logits)
        if self.scfg.temperature <= 0 and self.scfg.multistream:
            return greedy_argmax_multistream(logits)
        if self.scfg.temperature > 0 and self.scfg.multistream:
            # sampling prep runs as a descriptor program on the mesh;
            # the host only draws the Gumbel noise
            g = rng.gumbel(size=np.asarray(logits).shape)
            return temperature_sample_multistream(
                logits, self.scfg.temperature, g, self.scfg.min_logit)
        logits = np.asarray(logits, np.float32)
        if self.scfg.temperature <= 0:
            return logits.argmax(-1)
        z = logits / self.scfg.temperature
        z = z - z.max(-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(-1, keepdims=True)
        return np.array([rng.choice(len(q), p=q) for q in p])

    def generate(self, prompts: List[np.ndarray],
                 extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Greedy/temperature generation for a batch of same-length prompts.

        Returns the ``completions``, ``prefill_s`` (the call's start to
        the first tokens on the host: the batch's time to first token)
        and ``decode_tok_per_s``; MoE configs also return
        ``moe_assigned``, the assignments routed to the held experts over
        the call's layers and forward passes, and
        ``moe_max_expert_load``, the most of them one held expert
        received (counted on the device, read once at the end). Spans
        (``repro.core.spans``): ``serve.generate`` (stats ``call``,
        ``batch``, ``prompt_len``, and the two MoE counts) holds
        ``serve.prefill`` and one ``serve.step`` (``step``,
        ``active``: rows not done) per loop iteration; each holds a
        ``serve.forward`` and a ``serve.sample`` (``phase`` prefill or
        decode), and a step first runs ``serve.emit``, the token
        bookkeeping.
        """
        scfg = self.scfg
        rng = np.random.default_rng(scfg.seed)
        b = len(prompts)
        plen = len(prompts[0])
        assert all(len(p) == plen for p in prompts), "same-length prompts"
        # the unembedding is padded past the vocabulary (see
        # ArchConfig.padded_vocab); padded ids are never sampled
        vocab = self.cfg.vocab
        t0 = time.perf_counter()
        with span("serve.generate", call=next(_CALL_IDS), batch=b,
                  prompt_len=plen) as call:
            with span("serve.prefill"):
                tokens = jnp.asarray(np.stack(prompts), jnp.int32)
                batch = {"tokens": tokens, "labels": jnp.zeros_like(tokens)}
                if extra:
                    batch.update(extra)
                with span("serve.forward", phase="prefill"):
                    logits, cache, fill = self.model.prefill(
                        self.params, batch, cache_len=scfg.max_seq)
                with span("serve.sample", phase="prefill"):
                    cur = self._sample(logits[:, :vocab], rng, prefill=True)
            t1 = time.perf_counter()

            out = [[] for _ in range(b)]
            done = np.zeros(b, bool)
            fill = jnp.int32(fill)
            steps = 0
            for i in range(scfg.max_new_tokens):
                with span("serve.step", step=i, active=b - int(done.sum())):
                    with span("serve.emit"):
                        for r in range(b):
                            if not done[r]:
                                out[r].append(int(cur[r]))
                                if cur[r] == scfg.eos_token:
                                    done[r] = True
                    if done.all():
                        break
                    with span("serve.forward", phase="decode"):
                        logits, cache = self._decode(
                            self.params, jnp.asarray(cur[:, None], jnp.int32),
                            cache, fill, absorbed_mla=self.cfg.mla_absorb)
                    fill = fill + 1
                    with span("serve.sample", phase="decode"):
                        cur = self._sample(logits[:, -1, :vocab], rng)
                    steps += 1
            decode_s = time.perf_counter() - t1
            result = {"completions": out,
                      "prefill_s": t1 - t0,
                      "decode_tok_per_s": (steps * b / decode_s)
                      if decode_s else 0.0}
            if "moe_load" in cache:
                load = np.asarray(cache["moe_load"])
                counts = {"moe_assigned": int(load.sum()),
                          "moe_max_expert_load": int(load.max())}
                result.update(counts)
                call.set_metadata(**counts)
        return result
