"""Distributed-layer tests on 8 emulated host devices.

The device count must be set before jax initialises, and other tests need
the default single device — so these tests run the multi-device work in a
SUBPROCESS with XLA_FLAGS set (the same pattern the dry-run uses).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compressed_psum_and_ring_collectives():
    r = run_in_subprocess(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import collectives, overlap
        from repro.distributed.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 1000)).astype(np.float32)
        f = jax.shard_map(lambda v: collectives.compressed_psum_mean(v[0], "data")[None],
                      mesh=mesh, in_specs=P("data", None),
                      out_specs=P("data", None))
        got = np.asarray(jax.jit(f)(x))
        rel = float(np.abs(got - x.mean(0)).max() / np.abs(x.mean(0)).max())
        xs = rng.standard_normal((64, 32)).astype(np.float32)
        w = rng.standard_normal((32, 48)).astype(np.float32)
        f2 = jax.shard_map(lambda xl, wl: overlap.ring_allgather_matmul(xl, wl, "data"),
                       mesh=mesh, in_specs=(P("data", None), P(None, "data")),
                       out_specs=P(None, "data"))
        ag_ok = bool(np.allclose(jax.jit(f2)(xs, w), xs @ w, atol=1e-4))
        w2 = rng.standard_normal((32, 16)).astype(np.float32)
        f3 = jax.shard_map(lambda xl, wl: overlap.ring_matmul_reducescatter(xl, wl, "data"),
                       mesh=mesh, in_specs=(P(None, "data"), P("data", None)),
                       out_specs=P("data", None))
        rs_ok = bool(np.allclose(jax.jit(f3)(xs, w2), xs @ w2, atol=1e-3))
        print(json.dumps({"rel": rel, "ag_ok": ag_ok, "rs_ok": rs_ok}))
    """))
    assert r["rel"] < 0.02
    assert r["ag_ok"] and r["rs_ok"]


def test_pipeline_parallelism():
    r = run_in_subprocess(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import pipeline
        from repro.distributed.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        params = rng.standard_normal((8, 16)).astype(np.float32)
        xs = rng.standard_normal((12, 4, 16)).astype(np.float32)
        body = lambda p, x: jnp.maximum(x + p, 0.0)
        run = pipeline.pipelined_apply(mesh, body, "data", P("data", None),
                                       P(None, None, None), P(None, None, None))
        got = np.asarray(jax.jit(run)(params, xs))
        want = xs
        for s in range(8):
            want = np.maximum(want + params[s], 0.0)
        print(json.dumps({"ok": bool(np.allclose(got, want, atol=1e-5))}))
    """))
    assert r["ok"]


def test_sharded_train_step_matches_single_device():
    """pjit train step on a (2, 4) mesh must produce the same loss and
    parameters as the single-device step (numerics at f32)."""
    r = run_in_subprocess(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models import Model
        from repro.optim import AdamWConfig, init_opt_state
        from repro.distributed.mesh import make_mesh
        from repro.runtime.train import make_train_step
        cfg = configs.get_reduced("llama3-8b").scaled(
            compute_dtype="float32", param_dtype="float32")
        model = Model(cfg)
        params = model.init(0)
        opt = init_opt_state(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)}
        opt_cfg = AdamWConfig(lr=1e-3)
        single = jax.jit(make_train_step(cfg, opt_cfg, mesh=None))
        copy = lambda t: jax.tree.map(jnp.copy, t)   # the step donates
        p1, o1, l1, _ = single(copy(params), copy(opt), batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.models.common import set_activation_sharding
        set_activation_sharding(mesh, ("data",), "model")
        with mesh:
            sharded = make_train_step(cfg, opt_cfg, mesh=mesh)
            p2, o2, l2, _ = sharded(params, opt, batch)
        set_activation_sharding()
        dl = abs(float(l1) - float(l2))
        dp = max(float(jnp.abs(a - b).max())
                 for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        print(json.dumps({"dl": dl, "dp": dp}))
    """))
    assert r["dl"] < 1e-4, r
    assert r["dp"] < 1e-4, r


def test_grad_accumulation_equivalence():
    """grad_accum=4 must match accum=1 up to fp tolerance."""
    r = run_in_subprocess(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models import Model
        from repro.optim import AdamWConfig, init_opt_state
        from repro.runtime.train import build_step_fn
        cfg = configs.get_reduced("llama3-8b").scaled(
            compute_dtype="float32", param_dtype="float32")
        model = Model(cfg)
        params = model.init(0)
        opt = init_opt_state(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)}
        ocfg = AdamWConfig(lr=1e-3)
        s1 = jax.jit(build_step_fn(cfg, ocfg))
        s4 = jax.jit(build_step_fn(cfg.scaled(grad_accum=4), ocfg))
        p1, _, l1, _ = s1(params, opt, batch)
        p4, _, l4, _ = s4(params, opt, batch)
        dl = abs(float(l1) - float(l4))
        dp = max(float(jnp.abs(a - b).max())
                 for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)))
        print(json.dumps({"dl": dl, "dp": dp}))
    """))
    assert r["dl"] < 5e-3, r   # loss is mean over different partitions
    assert r["dp"] < 1e-3, r


def test_int8_quantization_roundtrip():
    from repro.distributed.collectives import quantize_int8, dequantize_int8
    import numpy as np
    rng = np.random.default_rng(0)
    x = rng.standard_normal(1000).astype(np.float32)
    import jax.numpy as jnp
    q, s = quantize_int8(jnp.asarray(x))
    back = np.asarray(dequantize_int8(q, s))
    assert np.abs(back - x).max() <= float(s) * 0.51 + 1e-6
