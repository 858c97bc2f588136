"""What running on the chip needs from the code, checked on the CPU.

* every descriptor runs on the device: conv and stencil nests hit the
  tiled Pallas conv kernel, other nests the engine's jittable plan, and a
  nest with no on-device plan is an error rather than a host round trip;
* a candidate the compiler refuses is recorded, never silently dropped,
  and a failure that is not a refusal propagates;
* the launchers share their config flags, a failed restore raises, and
  the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says or to a
  fixed path in the checkout;
* meshes have Auto axes.
"""
import argparse

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import (Agu, Descriptor, ExecutionPolicy, Executor, Opcode,
                        Program, clear_measured_policy_cache, conv2d, engine,
                        stencil_pass)
from repro.core.dispatch import _match_correlation, dispatch
from repro.kernels import ops

RNG = np.random.default_rng(11)


def _mem(n=8192):
    return RNG.standard_normal(n).astype(np.float32)


# ----------------------------------------------------------------------
# descriptors on the device
# ----------------------------------------------------------------------
CORRELATIONS = {
    # (oh, ow, kh, kw) output plane of a valid conv over a 20 x 40 image
    "conv3x3": lambda: conv2d(18, 38, 3, 3, 0, 1000, 2000, 40),
    "conv5x3": lambda: conv2d(16, 38, 5, 3, 0, 1000, 2000, 40),
    "conv1x1": lambda: conv2d(20, 40, 1, 1, 0, 1000, 2000, 40),
    # one output row, the host iterating rows (no output row loop)
    "conv3x3_one_row": lambda: Descriptor(
        bounds=(3, 3, 38), opcode=Opcode.MAC, init_level=2, store_level=2,
        agu0=Agu(0, (1, 40, 1)), agu1=Agu(1000, (1, 3, 0)),
        agu2=Agu(2000, (0, 0, 1))),
    # 3-tap passes over an 18 x 38 window of a plane with pitch 40
    "stencil_rows": lambda: stencil_pass(18, 38, 3, 1, 40, 1000, 2000, 40),
    "stencil_cols": lambda: stencil_pass(18, 38, 3, 0, 1, 1000, 2000, 40),
    # an output window with the image's pitch (a strided store)
    "stencil_pitched_out": lambda: Descriptor(
        bounds=(3, 38, 18), opcode=Opcode.MAC, init_level=1, store_level=1,
        agu0=Agu(40, (1, 1, 40)), agu1=Agu(1000, (1, 0, 0)),
        agu2=Agu(3000, (0, 1, 40))),
}


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("name", sorted(CORRELATIONS))
def test_dispatch_correlation_matches_engine(name, backend):
    d = CORRELATIONS[name]()
    assert _match_correlation(d) is not None, name
    mem = _mem()
    want = engine.execute(d, mem)
    with ops.backend(backend):
        got = np.asarray(dispatch(d, jnp.asarray(mem)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_match_correlation_reads_conv_geometry():
    assert _match_correlation(conv2d(18, 38, 3, 3, 0, 1000, 2000, 40)) == \
        (18, 38, 3, 3, 40, 38)
    assert _match_correlation(
        stencil_pass(18, 38, 3, 0, 1, 1000, 2000, 40)) == (18, 38, 3, 1, 40,
                                                           38)


def test_dispatch_prefix_store_nest_is_an_error():
    """A running dot product written every step (store_level <
    init_level) has no on-device plan: dispatch names it instead of
    copying the image to the host."""
    d = Descriptor(bounds=(8, 4), opcode=Opcode.MAC, init_level=1,
                   store_level=0, agu0=Agu(0, (1, 8)), agu1=Agu(100, (1, 0)),
                   agu2=Agu(300, (1, 8)))
    with pytest.raises(NotImplementedError, match="no on-device plan"):
        dispatch(d, jnp.asarray(_mem(1024)))
    engine.execute(d, _mem(1024))              # the oracle still runs it


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("policy", ["serial", "fused"])
def test_program_conv2d_and_laplace2d_vs_numpy(policy, backend):
    h, w = 24, 150
    img = RNG.standard_normal((h, w)).astype(np.float32)
    ker = RNG.standard_normal((3, 3)).astype(np.float32)
    p = Program()
    himg, hker = p.buffer((h, w)), p.buffer((3, 3))
    hconv = p.conv2d(himg, hker)
    hcoef = p.buffer(3, init=[1.0, -2.0, 1.0])
    hlap = p.laplace2d(himg, hcoef)
    res = Executor(ExecutionPolicy(policy=policy, backend=backend)).run(
        p, {himg: img, hker: ker})
    x = img.astype(np.float64)
    want_conv = sum(float(ker[i, j]) * x[i:i + h - 2, j:j + w - 2]
                    for i in range(3) for j in range(3))
    want_lap = (x[2:, 1:-1] + x[:-2, 1:-1] + x[1:-1, 2:] + x[1:-1, :-2]
                - 4.0 * x[1:-1, 1:-1])
    np.testing.assert_allclose(res[hconv], want_conv, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(res[hlap], want_lap, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bad", [
    lambda p: p.conv2d(p.buffer(16), p.buffer((3, 3))),
    lambda p: p.conv2d(p.buffer((2, 2)), p.buffer((3, 3))),
    lambda p: p.laplace2d(p.buffer((8, 8)), p.buffer(4)),
    lambda p: p.laplace2d(p.buffer((2, 8)), p.buffer(3)),
])
def test_program_plane_ops_reject_bad_shapes(bad):
    with pytest.raises(ValueError):
        bad(Program())


@pytest.mark.parametrize("op", ["argmax", "argmin"])
def test_arg_reduce_refuses_more_than_2_24_elements(op):
    p = Program()
    p.reduce(op, p.buffer(1 << 24))            # the largest exact index
    with pytest.raises(ValueError, match="exact only up to"):
        p.reduce(op, p.buffer((1 << 24) + 1))


# ----------------------------------------------------------------------
# refusals are recorded, other failures propagate
# ----------------------------------------------------------------------
def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2


def _refused(x):
    """A compiled (not interpreted) Pallas kernel: the CPU backend refuses
    to lower it, as the chip's compiler refuses a bad block."""
    return pl.pallas_call(_double_kernel, out_shape=jax.ShapeDtypeStruct(
        x.shape, x.dtype))(x)


def _fails_when_run(x):
    """Compiles, then raises while it runs (a host callback that fails)."""
    def fail(v):
        raise RuntimeError("fault while running")
    return jax.pure_callback(fail, jax.ShapeDtypeStruct(x.shape, x.dtype), x)


def test_compile_or_refusal_separates_refusals_from_run_faults():
    x = jnp.ones((8, 128), jnp.float32)
    run, err = ops.compile_or_refusal(_refused, x)
    assert run is None and isinstance(err, ops.LOWERING_ERRORS)
    run, err = ops.compile_or_refusal(_fails_when_run, x)
    assert err is None
    with pytest.raises(jax.errors.JaxRuntimeError, match="fault while"):
        jax.block_until_ready(run(x))


def _fake_gemm(refuse=lambda c: False, exc=None, fail_when_run=False):
    def gemm_pallas(a, b, *, block_m, block_n, block_k, interpret):
        out = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
        if exc is not None:
            raise exc(f"bug in {(block_m, block_n, block_k)}")
        if refuse((block_m, block_n, block_k)):
            return _refused(out)
        return _fails_when_run(out) if fail_when_run else out
    return gemm_pallas


def test_measure_pick_records_a_refused_candidate(monkeypatch):
    ops.clear_autotune_cache()
    base = (256, 256, 256)
    cands = ops._candidate_blocks(512, 512, 512, base)
    bad = cands[0]
    monkeypatch.setattr(ops, "gemm_pallas", _fake_gemm(lambda c: c == bad))
    pick = ops._measure_pick(512, 512, 512, base)
    assert pick != bad and pick in cands
    assert ops.block_cache_stats()["refused"] == 1
    ops.clear_autotune_cache()


def test_measure_pick_raises_when_every_candidate_is_refused(monkeypatch):
    ops.clear_autotune_cache()
    monkeypatch.setattr(ops, "gemm_pallas", _fake_gemm(lambda c: True))
    with pytest.raises(ValueError, match="interpret mode"):
        ops._measure_pick(512, 512, 512, (256, 256, 256))
    ops.clear_autotune_cache()


@pytest.mark.parametrize("fake,exc", [
    (_fake_gemm(exc=TypeError), TypeError),                 # a bug
    (_fake_gemm(exc=ValueError), ValueError),               # a bug
    (_fake_gemm(fail_when_run=True), jax.errors.JaxRuntimeError)])
def test_measure_pick_propagates_a_failure_that_is_not_a_refusal(
        monkeypatch, fake, exc):
    ops.clear_autotune_cache()
    monkeypatch.setattr(ops, "gemm_pallas", fake)
    with pytest.raises(exc):
        ops._measure_pick(512, 512, 512, (256, 256, 256))
    assert ops.block_cache_stats()["refused"] == 0
    ops.clear_autotune_cache()


def _race(monkeypatch, refuse=lambda pol: False, exc=None,
          fail_when_run=()):
    clear_measured_policy_cache()
    real = Executor._build_runner

    def build(self, descs, policy):
        if exc is not None and policy == "fused":
            raise exc(f"bug in {policy}")
        runner, source = real(self, descs, policy)
        if refuse(policy):
            return (lambda mem: _refused(runner(mem))), source
        if policy in fail_when_run:
            return (lambda mem: _fails_when_run(runner(mem))), source
        return runner, source
    monkeypatch.setattr(Executor, "_build_runner", build)
    p = Program()
    x = p.buffer(256)
    p.relu(x, out=x)
    ex = Executor(ExecutionPolicy(autotune="measure"))
    try:
        ex.run(p, {x: np.linspace(-1, 1, 256, dtype=np.float32)})
        return ex.stats
    finally:
        clear_measured_policy_cache()


def test_race_policies_records_a_refused_policy(monkeypatch):
    stats = _race(monkeypatch, lambda pol: pol == "multistream")
    g = stats["gains"]
    assert list(g["refused"]) == ["multistream"]
    assert "multistream" not in g["measured"]
    assert stats["policy"] in g["measured"]


def test_race_policies_raises_when_every_policy_is_refused(monkeypatch):
    with pytest.raises(ValueError, match="interpret mode"):
        _race(monkeypatch, lambda pol: True)


@pytest.mark.parametrize("kw,exc", [
    (dict(exc=TypeError), TypeError),
    (dict(fail_when_run=("fused",)), jax.errors.JaxRuntimeError)])
def test_race_policies_propagates_a_failure_that_is_not_a_refusal(
        monkeypatch, kw, exc):
    with pytest.raises(exc):
        _race(monkeypatch, **kw)


# ----------------------------------------------------------------------
# training donates its state
# ----------------------------------------------------------------------
@pytest.mark.parametrize("param_dtype", ["float32", "bfloat16"])
def test_trainer_steps_with_donated_state(tmp_path, param_dtype):
    """The train step donates params and optimizer state; with fp32 params
    the fp32 master copy must be its own buffer, or one buffer would be
    donated twice."""
    from repro import configs
    from repro.optim import AdamWConfig, init_opt_state
    from repro.runtime import TrainConfig, Trainer
    cfg = configs.get_reduced("granite-3-8b").scaled(
        n_layers=1, param_dtype=param_dtype)
    p = jnp.ones((4, 4), getattr(jnp, param_dtype))
    assert init_opt_state({"w": p})["master"]["w"] is not p
    r = Trainer(cfg, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=2),
                TrainConfig(steps=2, log_every=0, ckpt_every=2,
                            ckpt_dir=str(tmp_path / "ckpt"), global_batch=2,
                            seq_len=16, multistream_plan=False)).run()
    assert len(r["losses"]) == 2 and np.isfinite(r["losses"]).all()
    assert {str(x.dtype) for x in jax.tree.leaves(r["params"])} == {
        param_dtype}


# ----------------------------------------------------------------------
# launchers
# ----------------------------------------------------------------------
def test_parse_overrides_types_values():
    from repro.launch.common import parse_overrides
    assert parse_overrides(["n_layers=16", "rope_theta=1e4", "tie=true",
                            "compute_dtype=float32"]) == {
        "n_layers": 16, "rope_theta": 1e4, "tie": True,
        "compute_dtype": "float32"}


@pytest.mark.parametrize("launcher", ["serve", "train"])
def test_launchers_serve_published_widths_unless_reduced(launcher):
    import importlib
    from repro import configs
    from repro.launch.common import config_from_args
    parse = importlib.import_module(f"repro.launch.{launcher}")._parse
    full = config_from_args(parse(["--arch", "granite-3-8b",
                                   "--set", "n_layers=16"]))
    pub = configs.get("granite-3-8b")
    assert (full.d_model, full.d_ff, full.vocab, full.n_layers) == \
        (pub.d_model, pub.d_ff, pub.vocab, 16)
    small = config_from_args(parse(["--arch", "granite-3-8b", "--reduced"]))
    assert small == configs.get_reduced("granite-3-8b")


def test_serve_launcher_failed_restore_raises(tmp_path):
    from repro.launch import serve
    args = serve._parse(["--arch", "llama3-8b", "--reduced",
                         "--ckpt", str(tmp_path / "empty")])
    with pytest.raises(FileNotFoundError):
        serve.load_server(args)


def test_compile_cache_respects_the_environment(monkeypatch, tmp_path):
    from repro.launch import common
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert common.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(common.CHECKOUT / ".jax_cache")
        assert common.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert (common.CHECKOUT / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ----------------------------------------------------------------------
# meshes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("shape,axes", [((1,), ("cluster",)),
                                        ((1, 1), ("data", "model"))])
def test_make_mesh_has_auto_axes(shape, axes):
    from jax.sharding import AxisType
    from repro.distributed.mesh import make_mesh
    mesh = make_mesh(shape, axes)
    assert mesh.axis_names == axes
    assert tuple(mesh.devices.shape) == shape
    assert mesh.axis_types == (AxisType.Auto,) * len(axes)
    assert mesh.devices.reshape(-1)[0] == jax.devices()[0]
