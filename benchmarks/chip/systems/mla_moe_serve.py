"""A DeepSeek-V2 decoder (MLA, dense then MoE layers with shared experts)
served through ``Server.generate``: one client, closed loop, whole
``generate`` calls back to back, as ``lm_serve`` serves a dense model.

The configuration file is the model's published ``config.json`` with the
routed experts cut to the share this chip holds (``n_routed_experts``,
from ``expert_offset``; the published count under ``published``); ``arch``
refuses a file that states what the program does not compute. The
traffic file is read as ``lm_serve`` reads it. Weights
(``reference.mla_moe_weights``) and prompts are made from the seed.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

import reference.mla_moe_weights as weights
from systems.lm_serve import State, _check_layout, sample, widest_gap

#: what the program computes and a DeepSeek-V2 config states
PROGRAM_FIXED = {"model_type": "deepseek_v2", "hidden_act": "silu",
                 "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
                 "attention_bias": False, "q_lora_rank": None,
                 "scoring_func": "softmax", "topk_method": "greedy",
                 "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1,
                 "moe_layer_freq": 1}


def arch(config: Dict):
    """The program's ``ArchConfig`` for the configuration file."""
    from repro.models import ArchConfig, Yarn
    for k, want in PROGRAM_FIXED.items():
        if config[k] != want:
            raise ValueError(f"the program computes {k}={want!r}, the "
                             f"configuration states {config[k]!r}")
    ys = config["rope_scaling"]
    if ys["type"] != "yarn":
        raise ValueError(f"the program scales rope by YaRN only, the "
                         f"configuration states {ys['type']!r}")
    serving = config["serving"]
    return ArchConfig(
        name=config["name"], family="moe",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"],
        moe=True, n_experts=weights.routed_experts(config),
        expert_offset=config["expert_offset"],
        experts_held=config["n_routed_experts"],
        n_shared_experts=config["n_shared_experts"],
        top_k=config["num_experts_per_tok"],
        d_ff_expert=config["moe_intermediate_size"],
        first_dense_layers=config["first_k_dense_replace"],
        norm_topk_prob=config["norm_topk_prob"],
        mla=True, kv_lora_rank=config["kv_lora_rank"],
        rope_head_dim=config["qk_rope_head_dim"],
        nope_head_dim=config["qk_nope_head_dim"],
        v_head_dim=config["v_head_dim"],
        yarn=Yarn(factor=float(ys["factor"]),
                  original_max_position=ys["original_max_position_embeddings"],
                  beta_fast=float(ys["beta_fast"]),
                  beta_slow=float(ys["beta_slow"]), mscale=ys["mscale"],
                  mscale_all_dim=ys["mscale_all_dim"]),
        mla_absorb=serving["mla_absorb"],
        prefill_microbatch=serving["prefill_microbatch"],
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"])


def moe_layers(config: Dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def token_flops(config: Dict) -> Dict[str, float]:
    """Model operations per token outside attention's query-key pairs, 2
    per weight: the MLA projections (``wq``, ``wdkv``, ``wo``) of every
    layer, the dense layers' MLP, and per MoE layer the router, the held
    experts at their expected share of a token (``top_k * held / routed``
    experts) and the shared experts."""
    d, ffe = config["hidden_size"], config["moe_intermediate_size"]
    proj = 2 * sum(int(np.prod(s)) for n, s in
                   weights.attn_shapes(config).items()
                   if n in ("mixer/wq", "mixer/wdkv", "mixer/wo"))
    dense = 2 * sum(int(np.prod(s)) for s in
                    weights.dense_shapes(config).values())
    share = (config["num_experts_per_tok"] * config["n_routed_experts"]
             / weights.routed_experts(config))
    moe = (2 * sum(int(np.prod(s)) for s in
                   weights.moe_shapes(config).values())
           + share * 6 * d * ffe)
    return {"proj": proj, "dense": dense, "moe": moe,
            "layers": (config["num_hidden_layers"] * proj
                       + weights.n_dense(config) * dense
                       + moe_layers(config) * moe)}


def work(config: Dict, traffic: Dict) -> Dict[str, float]:
    """Model operations one ``generate`` call needs, from the shapes: the
    layers' weights (``token_flops``); prefill attention in the expanded
    form (each prompt token's latent up-projected to keys and values, then
    ``2 * heads * (nope + rope + v)`` per causal query-key pair); decode
    attention in the absorbed form (the query's and the output's
    up-projection absorbed, ``2 * heads * (2 * rank + rope)`` per cached
    key); the unembedding of the sampled positions only. A call samples
    ``new_tokens`` per request, so it needs ``new_tokens - 1`` decode
    steps."""
    b, s, new = traffic["batch"], traffic["prompt_len"], traffic["new_tokens"]
    n, d, v = (config["num_hidden_layers"], config["hidden_size"],
               config["vocab_size"])
    h, r = config["num_attention_heads"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    per_tok = token_flops(config)["layers"]
    expand = 2 * r * h * (dn + dv)
    pair = 2 * h * (dn + dr + dv)
    prefill = b * (s * (per_tok + n * expand)
                   + n * pair * s * (s + 1) // 2 + 2 * d * v)
    absorb = 2 * h * dn * r + 2 * h * r * dv
    key = 2 * h * (2 * r + dr)
    decode = sum(b * (per_tok + n * (absorb + key * (s + j + 1)) + 2 * d * v)
                 for j in range(new - 1))
    return {"flops": float(prefill + decode), "decode_steps": new - 1,
            "tokens": b * new}


def gmm_work(config: Dict, assigned: int, passes: int) -> Dict[str, float]:
    """Least work of the held experts' grouped GEMMs: ``6 * d * ffe``
    operations per assignment counted by the program (``moe_assigned``);
    bytes: the held experts' weights read once per MoE layer per forward
    pass (a prefill or a decode step), and each assigned token's row in
    and its result out."""
    import jax.numpy as jnp
    d, ffe = config["hidden_size"], config["moe_intermediate_size"]
    item = jnp.dtype(config["torch_dtype"]).itemsize
    w = config["n_routed_experts"] * 3 * d * ffe * item
    return {"flops": 6.0 * d * ffe * assigned,
            "bytes": float(w * moe_layers(config) * passes
                           + assigned * 2 * d * item)}


def setup(config: Dict, traffic: Dict, key, span) -> State:
    import jax
    from repro.runtime import ServeConfig, Server
    st = State()
    st.config, st.traffic, st.key = config, traffic, key
    cfg = arch(config)
    with span("bench.weights"):
        params = jax.jit(lambda k: weights.params(config, k))(key)
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
    calls, assigned, most = [], 0, 0
    with span("bench.window"):
        start = time.perf_counter()
        while True:
            i = len(calls) % len(st.prompts)
            with span("bench.generate"):
                out = st.server.generate(st.prompts[i])
            t1 = time.perf_counter()
            calls.append((i, out["completions"]))
            assigned += out.get("moe_assigned", 0)
            most = max(most, out.get("moe_max_expert_load", 0))
            if t1 - start >= seconds:
                break
    tokens = sum(len(c) for _, comps in calls for c in comps)
    passes = len(calls) * (1 + st.traffic["new_tokens"])
    return {"attempted": sum(len(comps) for _, comps in calls),
            "calls": len(calls), "window_s": t1 - start, "served": calls,
            "moe_assigned": assigned, "moe_max_expert_load": most,
            "gmm_work": gmm_work(st.config, assigned, passes),
            "metrics": {"serve_tok_s": tokens / (t1 - start)}}


def check(st: State, run: Dict, rng) -> tuple:
    """As ``lm_serve.check``, scored by the DeepSeek-V2 reference."""
    from reference.mla_moe_f32 import served_gaps
    failed, requests = sample(st, run, rng)
    gaps = served_gaps(st.config, st.key, requests)["served"]
    limit = st.traffic["checks"]["served_logit_gap"]
    failed += sum(1 for g in gaps if not g.max() <= limit)
    return failed, {"served_logit_gap": (widest_gap(gaps), limit)}

