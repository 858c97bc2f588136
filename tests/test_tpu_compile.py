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
