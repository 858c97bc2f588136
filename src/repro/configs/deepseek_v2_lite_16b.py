"""deepseek-v2-lite-16b [moe]: DeepSeek-V2-Lite at its published values
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json].

27 layers at hidden size 2048, untied vocabulary of 102400. Attention is
MLA with 16 heads: ``kv_lora_rank`` 512, no query compression, key heads
of 128 (no rope) + 64 (rope), value heads of 128; rope theta 1e4 scaled
by YaRN (factor 40 over an original 4096 positions, ``beta_fast`` 32,
``beta_slow`` 1, ``mscale`` = ``mscale_all_dim`` = 0.707). Layer 0 has a
dense SwiGLU MLP of width 10944 (``first_k_dense_replace`` 1); layers
1-26 route each token to 6 of 64 experts of width 1408 by softmax and
greedy top-k, without renormalising the gates (``norm_topk_prob`` false,
``routed_scaling_factor`` 1), plus 2 shared experts. Served with the
absorbed MLA decode.

All 64 routed experts are held by default; an expert-parallel share sets
``expert_offset`` / ``experts_held``.
"""
from repro.models import ArchConfig, Yarn

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400, rope_theta=1e4,
    moe=True, n_experts=64, n_shared_experts=2, top_k=6, d_ff_expert=1408,
    first_dense_layers=1, norm_topk_prob=False,
    mla=True, kv_lora_rank=512, rope_head_dim=64, nope_head_dim=128,
    v_head_dim=128, mla_absorb=True,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    grad_accum=4, prefill_microbatch=8,
)


def reduced():
    """Small widths for CPU tests; keeps the leading dense layer, the
    ungated top-k and YaRN."""
    return CONFIG.scaled(n_layers=3, d_model=128, n_heads=4, n_kv_heads=4,
                         d_ff=256, vocab=512, n_experts=8, top_k=2,
                         d_ff_expert=64, n_shared_experts=1, kv_lora_rank=64,
                         rope_head_dim=16, nope_head_dim=32, v_head_dim=32,
                         prefill_microbatch=2, notes="reduced smoke config")
