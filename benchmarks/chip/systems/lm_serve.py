"""A Qwen2 decoder LM served through ``Server.generate``: one client,
closed loop, whole ``generate`` calls back to back.

The configuration file is the model's published config as it is run;
``arch`` refuses one that states what the program does not compute. The
traffic file gives the batch, the prompt length, the number of new tokens
(every request runs to it: no end-of-sequence token), how many prompt
batches to draw, and how many served requests the check samples. Weights
(``reference.lm_weights``) and prompts are made from the seed.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

import reference.lm_weights as lm_weights


#: what the program's dense decoder computes and a Qwen2 config states
#: (RMSNorm's epsilon is fixed in the program; Qwen2 ties no embeddings)
PROGRAM_FIXED = {"model_type": "qwen2", "hidden_act": "silu",
                 "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
                 "use_sliding_window": False}


def arch(config: Dict):
    """The program's ``ArchConfig`` for the configuration file."""
    from repro.models import ArchConfig
    for k, want in PROGRAM_FIXED.items():
        if config[k] != want:
            raise ValueError(f"the program computes {k}={want!r}, the "
                             f"configuration states {config[k]!r}")
    return ArchConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=lm_weights.head_dim(config),
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        rope_theta=config["rope_theta"], qkv_bias=True,
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"])


def layer_matmul_params(config: Dict) -> int:
    """Weights one token multiplies through, per layer (norms left out)."""
    return sum(int(np.prod(s)) for s in
               lm_weights.layer_shapes(config).values())


def work(config: Dict, traffic: Dict) -> Dict[str, float]:
    """Model operations one ``generate`` call needs, from the shapes:
    2 per weight per token through the layers; causal attention (QK and
    PV, 4 * heads * head_dim per query-key pair); the unembedding of the
    positions that are sampled (prefill unembeds only the last prompt
    position). A call samples ``new_tokens`` per request, so it needs
    ``new_tokens - 1`` decode steps."""
    b, s, new = traffic["batch"], traffic["prompt_len"], traffic["new_tokens"]
    n, d, v = (config["num_hidden_layers"], config["hidden_size"],
               config["vocab_size"])
    per_tok = 2 * n * layer_matmul_params(config)
    attn_pair = 4 * n * config["hidden_size"]
    prefill = b * (s * per_tok + attn_pair * s * (s + 1) // 2 + 2 * d * v)
    decode = sum(b * (per_tok + attn_pair * (s + j + 1) + 2 * d * v)
                 for j in range(new - 1))
    return {"flops": float(prefill + decode), "decode_steps": new - 1,
            "tokens": b * new}


class State:
    pass


def _check_layout(cfg, params) -> None:
    """The benchmark's weight tree has the program's layout."""
    import jax
    from repro.models import Model
    want = jax.eval_shape(Model(cfg).init, 0)
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the benchmark's weights do not match the "
                           "program's parameter layout")


def setup(config: Dict, traffic: Dict, key, span) -> State:
    import jax
    from repro.runtime import ServeConfig, Server
    st = State()
    st.config, st.traffic, st.key = config, traffic, key
    cfg = arch(config)
    with span("bench.weights"):
        params = jax.jit(lambda k: lm_weights.params(config, k))(key)
        _check_layout(cfg, params)
        jax.block_until_ready(params)
    b, s, new = traffic["batch"], traffic["prompt_len"], traffic["new_tokens"]
    st.server = Server(cfg, params, ServeConfig(
        max_seq=s + new, max_new_tokens=new, eos_token=-1, temperature=0.0))
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key)))
    st.prompts = [list(rng.integers(0, config["vocab_size"], (b, s)))
                  for _ in range(traffic["prompt_sets"])]
    st.work = work(config, traffic)
    with span("bench.warm"):
        st.server.generate(st.prompts[0])
    return st


def window(st: State, seconds: float, span, rng) -> Dict:
    calls = []
    with span("bench.window"):
        start = time.perf_counter()
        while True:
            i = len(calls) % len(st.prompts)
            with span("bench.generate"):
                out = st.server.generate(st.prompts[i])
            t1 = time.perf_counter()
            calls.append((i, out["completions"]))
            if t1 - start >= seconds:
                break
    tokens = sum(len(c) for _, comps in calls for c in comps)
    return {"attempted": sum(len(comps) for _, comps in calls),
            "calls": len(calls), "window_s": t1 - start, "served": calls,
            "metrics": {"serve_tok_s": tokens / (t1 - start)}}


def sample(st: State, run: Dict, rng) -> tuple:
    """(malformed requests, sampled (prompt, served) pairs), and frees the
    server: a call that returned fewer than ``batch`` completions, or a
    completion of the wrong length or with an id outside the vocabulary,
    is malformed; the sample is drawn from the seed among the rest."""
    new, vocab = st.traffic["new_tokens"], st.config["vocab_size"]
    b = st.traffic["batch"]
    failed = sum(b - len(comps) for _, comps in run["served"])
    served = [(st.prompts[i][row], np.asarray(c))
              for i, comps in run["served"] for row, c in enumerate(comps)]
    ok = [r for r in served if len(r[1]) == new and r[1].min() >= 0
          and r[1].max() < vocab]
    failed += len(served) - len(ok)
    st.server = None
    run["served"] = None
    gc.collect()
    pick = rng.choice(len(ok), size=min(len(ok), st.traffic["check_requests"]),
                      replace=False)
    return failed, [(np.asarray(ok[j][0]), ok[j][1]) for j in sorted(pick)]


def widest_gap(gaps) -> float:
    """The widest gap below the reference's best logit over the served
    tokens of a sample (infinite for an empty one)."""
    return float(np.concatenate(gaps).max()) if gaps else float("inf")


def check(st: State, run: Dict, rng) -> tuple:
    """Malformed requests fail outright; a sample of the served requests,
    drawn from the seed, is scored by the float32 reference: the widest
    gap of a served token below the reference's best logit."""
    from reference.lm_f32 import served_gaps
    failed, requests = sample(st, run, rng)
    gaps = served_gaps(st.config, st.key, requests)["served"]
    limit = st.traffic["checks"]["served_logit_gap"]
    failed += sum(1 for g in gaps if not g.max() <= limit)
    return failed, {"served_logit_gap": (widest_gap(gaps), limit)}
