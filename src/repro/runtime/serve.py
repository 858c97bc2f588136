"""Batched serving loop: prefill + decode with a pre-allocated KV cache.

Continuous-batching-lite: a fixed decode batch of slots; finished requests
(EOS or max-len) are replaced by queued requests whose prompts are
prefilled into the freed slot. Works for all decoder archs, including
SSM/hybrid state caches.

Greedy sampling is one jitted ARGMAX over the rows of the logits on the
device (``greedy_argmax_multistream``); its ids feed the next decode step
where they lie, and the host reads each step's ids while the next step
runs, one step behind. Temperature sampling is a descriptor
:class:`~repro.core.program.Program` run through the policy-driven
:class:`~repro.core.executor.Executor`: sampling prep is the streaming
chain scale-by-temperature (AXPY ``logits/T + gumbel`` — the Gumbel-max
identity makes the added noise an exact draw from the softmax
distribution) -> optional THRESH prune -> ARGMAX chain-reduce tail, one
fused pass per request, regression-tested against ``jax.nn.softmax``
sampling. The greedy descriptor programs stay as library functions
(``greedy_argmax_program``, ``greedy_argmax_pipelined``): each request's
ARGMAX over its logits row is an independent sub-stream (disjoint
buffers), so the batch partitions request-per-cluster and executes
concurrently on the mesh — the serving-side use of the paper's
independent per-cluster streams. No hand-computed base addresses: the
program's allocator owns the layout.
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
from repro.models.transformer import decode_onepass_layers


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 512
    max_new_tokens: int = 32
    eos_token: int = 1
    temperature: float = 0.0
    seed: int = 0
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


def greedy_argmax_program(logits) -> np.ndarray:
    """Greedy sampling as a multi-cluster descriptor program.

    One ARGMAX command per request row — independent uniform sub-streams
    the scheduler can stack (vmap/shard_map lanes), cached per batch
    shape. Ties resolve to the first maximum, matching ``np.argmax``.
    Returns host int64 ids, the slots read by ``ProgramResult.take``.
    """
    logits = jnp.asarray(logits, jnp.float32)
    b, vocab = logits.shape
    return _run_sampler(
        _sampler_entry(_ARGMAX_PROGRAMS, b, vocab, staged=False,
                       policy="multistream"), logits)


@jax.jit
def greedy_argmax_multistream(logits) -> jax.Array:
    """Greedy sampling on the device: the ARGMAX over each row of a
    ``(b, vocab)`` array as one jitted program, returning device int32
    ids. Ties resolve to the first maximum, as the NTX ARGMAX command and
    ``np.argmax`` do. ``Server`` looks it up by this name when it samples,
    and takes numpy ids from a stand-in as well."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


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
        model, vocab = self.model, cfg.vocab

        def decode(params, ids, cache, fill, absorbed_mla=False):
            """One decode step on the ``(b,)`` sampled ids: the logits over
            the vocabulary (the padded ids are never sampled; see
            ArchConfig.padded_vocab), the cache, and ``fill + 1``, so
            nothing of a step runs outside the program."""
            logits, cache = model.decode(params, ids[:, None], cache, fill,
                                         absorbed_mla=absorbed_mla)
            return logits[:, -1, :vocab], cache, fill + 1

        # compiled as ``jit_decode``; the cache is donated: each step
        # updates it in place
        self._decode = jax.jit(decode, static_argnames=("absorbed_mla",),
                               donate_argnames=("cache",))
        install_gc_span()

    def _sample(self, logits, rng) -> jax.Array:
        """The next ids of a ``(b, vocab)`` array of logits, as device
        int32: greedy ones are the device's own, others are uploaded.
        ``greedy_argmax_multistream`` is looked up when called, so a
        stand-in that returns numpy ids drives the loop as well."""
        scfg = self.scfg
        if scfg.temperature <= 0:
            ids = greedy_argmax_multistream(logits)
        else:
            # sampling prep runs as a descriptor program on the mesh;
            # the host only draws the Gumbel noise
            g = rng.gumbel(size=logits.shape)
            ids = temperature_sample_multistream(
                logits, scfg.temperature, g, scfg.min_logit)
        ids = jnp.asarray(ids, jnp.int32)
        ids.copy_to_host_async()        # read a step later: start the copy
        return ids

    def generate(self, prompts: List[np.ndarray],
                 extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Greedy/temperature generation for a batch of same-length prompts.

        The host reads the ids one step behind: each iteration dispatches
        the decode step on the device ids of the last, and its sampler,
        and only then reads the last ids, emits them and checks EOS, so
        one step is queued on the device while the host works. A call
        dispatches ``max_new_tokens - 1`` decode steps (the last token
        needs no forward); when every row has met EOS the queued step is
        dropped.

        Returns the ``completions``, ``prefill_s`` (the call's start to
        the first tokens on the host: the batch's time to first token),
        ``decode_tok_per_s``, ``steps_ahead`` (decode steps dispatched
        before the previous ids were read) and ``steps_discarded`` (queued
        steps dropped at EOS); MoE configs also return ``moe_assigned``,
        the assignments routed to the held experts over the call's layers
        and forward passes, and ``moe_max_expert_load``, the most of them
        one held expert received (counted on the device, read once at the
        end). Spans (``repro.core.spans``): ``serve.generate`` (stats
        ``call``, ``batch``, ``prompt_len``, ``steps_ahead``,
        ``steps_discarded``, the two MoE counts, and
        ``mla_onepass_layers``: the layers of the compiled decode step
        that take the one-pass MLA kernel) holds
        ``serve.prefill`` and one ``serve.step`` (``step``, ``active``:
        rows not done) per token. The prefill holds a ``serve.forward``
        and a ``serve.sample`` (``phase`` prefill); a step holds the next
        step's ``serve.forward`` and ``serve.sample`` (``phase`` decode;
        none in the last step), then ``serve.readback`` (``bytes``), the
        host's wait for this step's ids, and ``serve.emit``, the token
        bookkeeping.
        """
        scfg = self.scfg
        rng = np.random.default_rng(scfg.seed)
        b = len(prompts)
        plen = len(prompts[0])
        assert all(len(p) == plen for p in prompts), "same-length prompts"
        vocab = self.cfg.vocab
        n = scfg.max_new_tokens
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
                    ids = self._sample(logits[:, :vocab], rng)

            out = [[] for _ in range(b)]
            done = np.zeros(b, bool)
            fill = jnp.int32(fill)
            ahead = discarded = 0
            t1 = t0
            for i in range(n):
                with span("serve.step", step=i, active=b - int(done.sum())):
                    nxt = None
                    if i + 1 < n:
                        with span("serve.forward", phase="decode"):
                            logits, cache, fill = self._decode(
                                self.params, ids, cache, fill,
                                absorbed_mla=self.cfg.mla_absorb)
                        with span("serve.sample", phase="decode"):
                            nxt = self._sample(logits, rng)
                        ahead += 1
                    with span("serve.readback", bytes=4 * b):
                        cur = np.asarray(ids)
                    if i == 0:
                        t1 = time.perf_counter()
                    with span("serve.emit"):
                        for r in range(b):
                            if not done[r]:
                                out[r].append(int(cur[r]))
                                if cur[r] == scfg.eos_token:
                                    done[r] = True
                    if done.all():
                        discarded = int(nxt is not None)
                        break
                    ids = nxt
            decode_s = time.perf_counter() - t1
            counts = {"steps_ahead": ahead, "steps_discarded": discarded}
            if "moe_load" in cache:
                load = np.asarray(cache["moe_load"])
                counts.update(moe_assigned=int(load.sum()),
                              moe_max_expert_load=int(load.max()))
            device = next(iter(jax.tree.leaves(cache)[0].devices()))
            call.set_metadata(**counts, mla_onepass_layers=(
                decode_onepass_layers(self.cfg, b, scfg.max_seq,
                                      device.platform, self.cfg.mla_absorb)))
        return {"completions": out,
                "prefill_s": t1 - t0,
                "decode_tok_per_s": ((ahead - discarded) * b / decode_s)
                if decode_s else 0.0, **counts}

