"""Numerical parity of the beyond-paper perf layouts (EXPERIMENTS.md §Perf):
context-parallel attention, absorbed MLA decode, and elastic mesh
re-scaling — each must be bit-for-behaviour equivalent to the baseline."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(code: str, devices: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_ctx_parallel_loss_parity():
    """ctx_parallel changes sharding only — the loss must be identical to
    the baseline layout on the same mesh (f32)."""
    r = run_in_subprocess(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models import Model
        from repro.distributed.mesh import make_mesh
        from repro.models.common import set_activation_sharding
        cfg = configs.get_reduced("llama3-8b").scaled(
            compute_dtype="float32", param_dtype="float32")
        mesh = make_mesh((2, 4), ("data", "model"))
        set_activation_sharding(mesh, ("data",), "model")
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)),
                                       jnp.int32)}
        m_base = Model(cfg)
        m_ctx = Model(cfg.scaled(ctx_parallel=True,
                                 ctx_replicate_weights=False))
        params = m_base.init(0)
        with mesh:
            l1, _ = jax.jit(m_base.loss)(params, batch)
            l2, _ = jax.jit(m_ctx.loss)(params, batch)
        set_activation_sharding()
        print(json.dumps({"d": abs(float(l1) - float(l2))}))
    """))
    assert r["d"] < 1e-4, r


def test_mla_absorbed_equals_expanded():
    from repro import configs
    from repro.models import Model
    cfg = configs.get_reduced("deepseek-v2-lite-16b").scaled(
        compute_dtype="float32", param_dtype="float32")
    # both forms carry YaRN's rope and softmax scale, past a dense layer 0
    assert cfg.yarn is not None and cfg.first_dense_layers == 1
    m = Model(cfg)
    p = m.init(0)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                                   jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    _, cache, fill = m.prefill(p, batch, cache_len=24)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (2, 1)), jnp.int32)
    l1, _ = m.decode(p, tok, cache, jnp.int32(fill), absorbed_mla=False)
    l2, _ = m.decode(p, tok, cache, jnp.int32(fill), absorbed_mla=True)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32),
                               rtol=1e-3, atol=1e-3)


def test_elastic_mesh_rescale():
    """Save a training state on a (4,2) mesh, restore it onto (2,4) — the
    elastic-scaling path (node loss / regrowth) — and continue stepping."""
    r = run_in_subprocess(textwrap.dedent("""
        import json, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro import configs
        from repro.checkpoint import CheckpointManager, reshard_checkpoint
        from repro.distributed import sharding as shd
        from repro.distributed.mesh import make_mesh
        from repro.models import Model
        from repro.optim import AdamWConfig, init_opt_state
        from repro.runtime.train import build_step_fn
        cfg = configs.get_reduced("llama3-8b").scaled(
            compute_dtype="float32", param_dtype="float32")
        model = Model(cfg)
        params = model.init(0)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                       jnp.int32)}
        step = jax.jit(build_step_fn(cfg, AdamWConfig(lr=1e-3)))

        mesh_a = make_mesh((4, 2), ("data", "model"))
        pshape = jax.eval_shape(lambda: model.init(0))
        sh_a = shd.param_shardings(mesh_a, pshape)
        params_a = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                params, sh_a)
        opt_a = init_opt_state(params_a)
        with mesh_a:
            p1, o1, l1, _ = step(params_a, opt_a, batch)
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(1, {"params": p1, "opt": o1})

        # 'rescale': new mesh shape, restore with the new shardings
        mesh_b = make_mesh((2, 4), ("data", "model"))
        sh_b = shd.param_shardings(mesh_b, pshape)
        params_b = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                params, sh_b)
        like = {"params": params_b, "opt": init_opt_state(params_b)}
        restored, stepno = mgr.restore(like)
        # the restore itself must be bit-exact (values identical; only the
        # device layout changed)
        md = max(jax.tree.leaves(jax.tree.map(
            lambda x, y: float(jnp.abs(jnp.asarray(x, jnp.float32)
                                       - jnp.asarray(y, jnp.float32)).max()),
            {"params": restored["params"], "opt": restored["opt"]},
            {"params": p1, "opt": o1})))
        with mesh_b:
            p2, o2, l2, _ = step(restored["params"], restored["opt"], batch)
        # the restored state must match the original continuation
        with mesh_a:
            p2a, o2a, l2a, _ = step(p1, o1, batch)
        print(json.dumps({"dl": abs(float(l2) - float(l2a)),
                          "maxdiff": md, "step": int(stepno)}))
    """))
    assert r["step"] == 1
    assert r["maxdiff"] == 0.0, r          # restore is bit-exact
    # the continuation loss is computed under a different SPMD partitioning
    # (model axis 2-way -> 4-way): f32 reduction order differs, so compare
    # with partition-noise tolerance rather than bitwise (measured noise on
    # this backend is ~1.3e-2 at loss ~10.9; 2x headroom)
    assert r["dl"] < 2.5e-2, r
