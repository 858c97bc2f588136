"""The main-path kernels compile for the TPU at real widths.

Each test lowers and compiles one kernel for a described ``v5e:2x2``
topology (no chip attached) under ``ops.backend("pallas")`` and checks that
the compiled program holds a Mosaic kernel (``tpu_custom_call``). This is
what interpret mode cannot show: block shapes off the 8 x 128 tiling,
primitives Mosaic cannot lower, kernels that overflow VMEM. A compile that
passes is not a run on the chip.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_has_kernel(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    with ops.backend("pallas"):
        compiled = jax.jit(fn).lower(*args).compile()
    return "tpu_custom_call" in compiled.as_text()


F32, BF16 = jnp.float32, jnp.bfloat16


def _multistream_argmax(logits):
    from repro.core.multistream import _stacked_run_fn
    from repro.runtime.serve import _ARGMAX_PROGRAMS, _sampler_entry
    b, vocab = logits.shape
    prog = _sampler_entry(_ARGMAX_PROGRAMS, b, vocab, staged=False,
                          policy="multistream")[0]
    from repro.core import ClusterScheduler
    sched = ClusterScheduler(prog.descriptors, n_clusters=1)
    mem = jnp.zeros(prog.size, jnp.float32)
    for i in range(b):
        lo, _ = prog.resolve(f"row{i}").span
        mem = jax.lax.dynamic_update_slice(mem, logits[i], (lo,))
    return _stacked_run_fn(sched.substreams, sharded=False)(mem)


def _command_stream_gemm(a, b, bias):
    from repro.core import CommandStream, Program
    m, k = a.shape
    n = b.shape[1]
    p = Program()
    ha, hb = p.buffer((m, k)), p.buffer((k, n))
    hbias = p.buffer((m, n))
    c = p.gemm(ha, hb)
    p.add(c, hbias, out=c)
    p.relu(c, out=c)
    mem = jnp.concatenate([a.reshape(-1), b.reshape(-1), bias.reshape(-1),
                           jnp.zeros(m * n, jnp.float32)])
    return CommandStream(p.descriptors).execute(mem)


CASES = {
    "gemm_f32_4096": (lambda a, b: ops.gemm(a, b),
                      [((4096, 4096), F32), ((4096, 4096), F32)]),
    "fused_mlp_swiglu_granite": (
        lambda x, w1, w2, w3: ops.fused_mlp(x, w1, w2, w3, act="swiglu",
                                            residual=x),
        [((2048, 4096), BF16), ((4096, 12800), BF16),
         ((12800, 4096), BF16), ((4096, 12800), BF16)]),
    "flash_prefill": (
        lambda q, k, v: ops.attention(q, k, v, causal=True),
        [((1, 32, 2048, 128), BF16), ((1, 8, 2048, 128), BF16),
         ((1, 8, 2048, 128), BF16)]),
    "flash_decode": (
        lambda q, k, v: ops.attention(q, k, v, causal=True, kv_len=1500),
        [((8, 32, 1, 128), BF16), ((8, 8, 2048, 128), BF16),
         ((8, 8, 2048, 128), BF16)]),
    "reduce_argmax_vocab": (lambda x: ops.reduce("argmax", x),
                            [((8, 49155), F32)]),
    "chain_reduce_argmax_vocab": (
        lambda x, y: ops.chain_reduce([("axpy", 0.5), ("relu", 0.0)],
                                      "argmax", x, (y,)),
        [((8, 49155), F32), ((8, 49155), F32)]),
    "elementwise_chain_2^24": (
        lambda x, y: ops.elementwise_chain([("axpy", 0.5), ("relu", 0.0)],
                                           x, (y,)),
        [((1, 1 << 24), F32), ((1, 1 << 24), F32)]),
    "elementwise_chain_blocked_2^24": (
        lambda x, y: ops.elementwise_chain([("axpy", 0.5), ("relu", 0.0)],
                                           x, (y,), block=8192),
        [((1, 1 << 24), F32), ((1, 1 << 24), F32)]),
    "adamw_granite_w1": (
        lambda p, g, m, v: ops.adamw_update(p, g, m, v, 1, lr=1e-3),
        [((4096, 12800), F32)] * 4),
    "sampler_stacked_vmap_argmax": (_multistream_argmax,
                                    [((8, 49155), F32)]),
    "command_stream_gemm_epilogue": (
        _command_stream_gemm,
        [((512, 1024), F32), ((1024, 512), F32), ((512, 512), F32)]),
    "ssd_scan": (
        lambda x, dt, A, B, C: ops.ssd(x, dt, A, B, C, chunk=128),
        [((1, 2048, 64, 64), F32), ((1, 2048, 64), F32), ((64,), F32),
         ((1, 2048, 128), F32), ((1, 2048, 128), F32)]),
    "conv2d_3x3_1024": (lambda img, ker: ops.conv2d(img, ker),
                        [((1024, 1024), F32), ((3, 3), F32)]),
    "laplace_512": (lambda x: ops.laplace(x), [((512, 512), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    assert _compile_has_kernel(fn, *shapes, sharding=one_chip), name


def test_mla_decode_step_reads_the_latent_stacks_in_place(one_chip):
    """``jit_decode`` of the reduced DeepSeek config (at a latent the
    one-pass kernel takes) compiled for v5e: every MLA layer's attention
    is the named kernel, and each latent stack reaches it whole, neither
    sliced to the layer nor copied to another layout."""
    import re
    from repro import configs
    from repro.kernels.mla_decode import BLOCK_K, KERNEL_NAME
    from repro.models import transformer
    from repro.runtime import Server, ServeConfig
    cfg = configs.get_reduced("deepseek-v2-lite-16b").scaled(
        kv_lora_rank=128)
    b, seq = 8, 2 * BLOCK_K

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(lambda: transformer.init_params(cfg, 0)))
    cache = shapes(jax.eval_shape(
        lambda: transformer.init_cache(cfg, b, seq)))
    srv = Server(cfg, None, ServeConfig(max_seq=seq, max_new_tokens=2,
                                        eos_token=-1))
    ids, fill = shapes((jnp.zeros((b,), jnp.int32), jnp.int32(0)))
    text = srv._decode.lower(params, ids, cache, fill,
                             absorbed_mla=True).compile().as_text()
    defs = dict(re.findall(r"^\s*(%[\w.-]+) = (.*)$", text, re.M))
    calls = [v for k, v in defs.items() if k.startswith(f"%{KERNEL_NAME}")]
    # one call in each segment's scan body: the dense layer and the MoE
    # layers
    assert len(calls) == 2
    # the jnp form's scores over the whole cache are gone
    h = cfg.n_heads
    assert not re.search(rf"= f32\[{b},{h},(1,)?{seq}\]", text)
    stacks = []
    for call in calls:
        operands = re.search(r"custom-call\(([^)]*)\)", call).group(1)
        c_kv, k_rope = re.findall(r"%[\w.-]+", operands)[4:6]
        for name in (c_kv, k_rope):
            producer = defs[name]
            assert not re.search(r"^%copy|dynamic-slice", name), name
            assert not re.search(r"\bcopy\(|dynamic-slice", producer), (
                name, producer[:200])
        # the c_kv operand is a whole stack, (layers of the kind, b, seq,
        # rank)
        shape = re.match(r"bf16\[(\d+),(\d+),(\d+),(\d+)\]", defs[c_kv])
        n, *rest = map(int, shape.groups())
        assert rest == [b, seq, cfg.kv_lora_rank], defs[c_kv][:80]
        stacks.append(n)
    assert sorted(stacks) == sorted(
        [cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers])
