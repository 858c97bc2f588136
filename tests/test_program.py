"""The ntx.Program builder + policy-driven Executor front door.

Covers the allocator (alignment, non-overlap, deterministic layout),
pack/unpack, descriptor lowering, the Executor's policy auto-selection
(mocked gain ratios -> expected policy), bit-equality of every execution
policy on fixed and random programs, removal of the old ``dispatch_*``
shims, the ARGMAX/ARGMIN chain tails and the handoff-aware stage LPT.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import ntx
from repro.core import (CommandStream, ExecutionPolicy, Executor, Opcode,
                        Program, engine)
from repro.core.dispatch import _match_gemm, dispatch
from repro.core.multistream import StageSchedule
from repro.core.stream import FusedChainReduce, plan_stream
from repro.kernels import ops

RNG = np.random.default_rng(13)

POLICIES = ("serial", "fused", "multistream", "pipeline")


def _arr(n):
    return RNG.standard_normal(n).astype(np.float32)


def _chain_program(n=256):
    """thresh -> relu -> axpy chain with an argmax tail, two inputs."""
    p = Program()
    x = p.buffer((n,), name="x")
    y = p.buffer((n,), name="y")
    t = p.thresh(x, 0.2)
    p.relu(t, out=t)
    out = p.axpy(1.5, t, y)
    s = p.reduce("argmax", out, name="amax")
    return p, x, y, out, s


# ----------------------------------------------------------------------
# Allocator
# ----------------------------------------------------------------------
def test_allocator_alignment_and_no_overlap():
    p = Program(align=8)
    handles = [p.buffer((int(n),)) for n in RNG.integers(1, 100, size=20)]
    spans = p.spans()
    for h, (lo, hi) in zip(handles, spans):
        assert lo % 8 == 0
        assert hi - lo == h.size
    for (al, ah), (bl, bh) in zip(spans, spans[1:]):
        assert ah <= bl, "buffers overlap"
    assert p.size == spans[-1][1]


def test_allocator_deterministic_layout():
    def build():
        p = Program()
        a = p.buffer((37,), name="a")
        b = p.buffer((5, 5), name="b")
        c = p.axpy(2.0, a, a)
        p.reduce("sum", c)
        return p
    assert build().spans() == build().spans()
    assert build().descriptors == build().descriptors


def test_allocator_rejects_bad_shapes_and_names():
    p = Program()
    p.buffer((4,), name="x")
    with pytest.raises(ValueError):
        p.buffer((4,), name="x")          # duplicate name
    with pytest.raises(ValueError):
        p.buffer((-1,))
    with pytest.raises(ValueError):
        Program(align=0)


def test_foreign_handle_rejected():
    p1, p2 = Program(), Program()
    x = p1.buffer((8,))
    with pytest.raises(ValueError):
        p2.relu(x)


# ----------------------------------------------------------------------
# pack / unpack
# ----------------------------------------------------------------------
def test_pack_unpack_roundtrip():
    p = Program()
    a = p.buffer((3, 4), name="a", init=np.arange(12, dtype=np.float32))
    b = p.buffer((5,), name="b")
    c = p.buffer((7,), name="c")
    data = _arr(5)
    mem = p.pack({b: data})
    res = p.unpack(mem)
    np.testing.assert_array_equal(res[a], np.arange(12).reshape(3, 4))
    np.testing.assert_array_equal(res["b"], data)       # by name too
    np.testing.assert_array_equal(res[c], np.zeros(7))  # default zeros
    # call-time binding overrides init
    mem2 = p.pack({a: np.ones(12, np.float32)})
    np.testing.assert_array_equal(p.unpack(mem2)[a], np.ones((3, 4)))


def test_pack_validates_sizes():
    p = Program()
    b = p.buffer((5,))
    with pytest.raises(ValueError):
        p.pack({b: np.zeros(6, np.float32)})
    with pytest.raises(ValueError):
        p.buffer((4,), init=np.zeros(3, np.float32))
    with pytest.raises(ValueError):
        p.unpack(jnp.zeros(p.size + 1, jnp.float32))


# ----------------------------------------------------------------------
# ProgramResult.take: read a few buffers without the whole image
# ----------------------------------------------------------------------
def _interleaved_program(n=40, lanes=3):
    """Per lane an input row, its relu and an argmax slot, declared in
    turn, so every slot sits between rows in the image."""
    p = Program()
    outs, slots = [], []
    for i in range(lanes):
        r = p.buffer((n,), name=f"r{i}", init=_arr(n))
        outs.append(p.relu(r))
        slots.append(p.argmax(outs[-1], name=f"s{i}"))
    return p, outs, slots


def _flat(res, keys):
    return np.concatenate([res[k].reshape(-1) for k in keys])


@pytest.mark.parametrize("policy", ["multistream", "pipeline", "fused"])
def test_take_reads_what_indexing_reads(policy):
    p, outs, slots = _interleaved_program()
    for keys in (slots, outs[::-1], [slots[1], outs[0], "s2"]):
        res = Executor(policy=policy).run(p)
        got = res.take(keys)
        assert res._np is None                  # the image stayed put
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, _flat(res, keys))


def test_take_after_numpy_reads_the_host_copy(monkeypatch):
    p, outs, slots = _interleaved_program()
    res = Executor(policy="fused").run(p)
    img = res.numpy()
    monkeypatch.setattr("repro.core.program._gather", None)  # no device read
    keys = [outs[2], slots[0]]
    np.testing.assert_array_equal(res.take(keys), _flat(res, keys))
    assert res.numpy() is img


def test_take_stages_its_index_once():
    p, outs, slots = _interleaved_program()
    ex = Executor(policy="multistream")
    first = ex.run(p).take(slots)
    (idx,) = p._take_cache.values()
    np.testing.assert_array_equal(ex.run(p).take(slots), first)
    assert len(p._take_cache) == 1                   # not staged again
    ex.run(p).take(slots[:1])                        # another list: its own
    assert len(p._take_cache) == 2
    assert p._take_cache[tuple(h.index for h in slots)] is idx


# ----------------------------------------------------------------------
# Descriptor lowering
# ----------------------------------------------------------------------
def test_gemm_lowering_matches_canonical_pattern():
    p = Program()
    A = p.buffer((6, 4), name="A", init=_arr(24))
    B = p.buffer((4, 5), name="B", init=_arr(20))
    C = p.gemm(A, B)
    assert _match_gemm(p.descriptors[0]) == (6, 5, 4)
    res = Executor(policy="fused").run(p)
    np.testing.assert_allclose(
        res[C], np.asarray(res[A]) @ np.asarray(res[B]), rtol=1e-5,
        atol=1e-5)


def test_gemv_and_laplace_lowering():
    p = Program()
    A = p.buffer((6, 9), name="A", init=_arr(54))
    x = p.buffer((9,), name="x", init=_arr(9))
    y = p.gemv(A, x)
    src = _arr(34)
    s = p.buffer((34,), name="s", init=src)
    coef = p.buffer((3,), name="coef", init=np.asarray([1.0, -2.0, 1.0]))
    lap = p.laplace1d(s, coef)
    res = Executor().run(p)
    np.testing.assert_allclose(res[y], res[A] @ res[x], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(res[lap],
                               src[:-2] - 2 * src[1:-1] + src[2:],
                               rtol=1e-4, atol=1e-4)


def test_chain_fuses_through_program_handles():
    """The builder's in-place chain lowers to descriptors plan_stream can
    fuse — handle plumbing must not break the §II-E fusion layer."""
    p, *_ = _chain_program()
    groups = plan_stream(p.descriptors)
    assert any(g.fused for g in groups)


# ----------------------------------------------------------------------
# Executor: every policy bit-equal, oracle-checked
# ----------------------------------------------------------------------
def test_all_policies_bit_equal_and_match_engine():
    n = 256
    p, x, y, out, s = _chain_program(n)
    inputs = {x: _arr(n), y: _arr(n)}
    ex = Executor()
    base = ex.run(p, inputs=inputs)
    assert ex.stats["policy"] in POLICIES
    # engine oracle (cycle-sequential float64 math) within kernel tolerance
    mo = np.asarray(p.pack(inputs))
    for d in p.descriptors:
        mo = engine.execute(d, mo)
    np.testing.assert_allclose(np.asarray(base.mem), mo, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(base[s],
                                  [np.argmax(base[out])])
    for pol in POLICIES:
        got = Executor(policy=pol).run(p, inputs=inputs)
        np.testing.assert_array_equal(np.asarray(got.mem),
                                      np.asarray(base.mem), err_msg=pol)


def _random_stream_program(rng):
    """Random streaming/reduction program over random symbolic buffers.

    Stays inside the streaming command set + reduce tails (GEMM equality
    is numeric, not bitwise — covered separately) and exercises chains,
    aliasing second operands, memset and every reduction tail."""
    p = Program()
    n = int(rng.integers(8, 300))
    bufs = [p.buffer((n,), name=f"b{i}",
                     init=rng.standard_normal(n).astype(np.float32))
            for i in range(4)]
    for _ in range(int(rng.integers(2, 10))):
        kind = int(rng.integers(0, 7))
        x, y, out = (bufs[int(rng.integers(0, len(bufs)))]
                     for _ in range(3))
        if kind == 0:
            p.thresh(x, float(rng.standard_normal()), out=out)
        elif kind == 1:
            p.relu(x, out=out)
        elif kind == 2:
            p.copy(x, out=out)
        elif kind == 3:
            getattr(p, rng.choice(["add", "sub", "mul", "mask"]))(
                x, y, out=out)
        elif kind == 4:
            p.axpy(float(rng.standard_normal()), x, y, out=out)
        elif kind == 5:
            p.set(out, float(rng.standard_normal()))
        else:
            p.reduce(str(rng.choice(["sum", "min", "max", "argmin",
                                     "argmax"])), x)
    return p


def test_random_programs_bit_equal_across_policies():
    """The satellite property: a random Program is bit-equal across all
    four policies (and the auto pick), every transport included."""
    for seed in range(20):
        rng = np.random.default_rng(seed)
        p = _random_stream_program(rng)
        base = np.asarray(Executor(policy="serial").run(p).mem)
        for pol in ("fused", "multistream", "pipeline", None):
            ex = Executor() if pol is None else Executor(policy=pol)
            got = np.asarray(ex.run(p).mem)
            np.testing.assert_array_equal(
                got, base, err_msg=f"seed {seed} policy {pol}")


# ----------------------------------------------------------------------
# Policy auto-selection
# ----------------------------------------------------------------------
def _fake_gains(fusion, multi, pipe, fits=1.0):
    return {"fusion": {"speedup": fusion},
            "multistream": {"speedup": multi},
            "pipeline": {"speedup": pipe},
            "tiling": {"speedup": 1.0, "fits": fits}}


@pytest.mark.parametrize("fusion,multi,pipe,want", [
    (1.0, 1.0, 1.0, "serial"),       # nothing helps -> simplest
    (2.5, 1.0, 1.0, "fused"),        # fusion only
    (2.0, 3.0, 1.2, "multistream"),  # mesh gain on top of fusion
    (1.5, 1.4, 2.8, "pipeline"),     # dependent stages dominate
    (0.9, 1.0, 1.0, "serial"),       # a pessimizing fusion stays serial
    (2.0, 1.7, 1.7, "multistream"),  # tie between mesh layers -> simpler
])
def test_auto_policy_selection_mocked_gains(monkeypatch, fusion, multi,
                                            pipe, want):
    monkeypatch.setattr("repro.perfmodel.ntx.policy_gains",
                        lambda *a, **k: _fake_gains(fusion, multi, pipe))
    chosen, gains = Executor().select_policy([])
    assert chosen == want
    assert set(gains["scores"]) == set(("serial",) + POLICIES)


def test_auto_policy_capacity_overrides_scores(monkeypatch):
    """A working set the TCDM cannot hold forces tiling no matter how
    good the resident policies look on paper."""
    monkeypatch.setattr("repro.perfmodel.ntx.policy_gains",
                        lambda *a, **k: _fake_gains(9.0, 9.0, 9.0,
                                                    fits=0.0))
    chosen, _ = Executor().select_policy([])
    assert chosen == "tiled"


def test_auto_policy_override_per_call():
    p, x, y, *_ = _chain_program(64)
    inputs = {x: _arr(64), y: _arr(64)}
    ex = Executor()                       # auto
    ex.run(p, inputs=inputs, policy="pipeline")
    assert ex.stats["policy"] == "pipeline"
    assert ex.stats["scheduler"]["n_stages"] >= 1
    with pytest.raises(ValueError):
        ex.run(p, inputs=inputs, policy="warp")


def test_plan_reports_policy_without_running():
    p, *_ = _chain_program(64)
    plan = Executor().plan(p)
    assert plan["policy"] in POLICIES
    assert set(plan["gains"]["scores"]) == set(("serial",) + POLICIES)
    assert Executor(policy="pipeline").plan(p)["policy"] == "pipeline"


# ----------------------------------------------------------------------
# ExecutionPolicy knobs: backend + autotune (NTX_AUTOTUNE replacement)
# ----------------------------------------------------------------------
def test_policy_validation():
    with pytest.raises(ValueError):
        ExecutionPolicy(policy="warp")
    with pytest.raises(ValueError):
        ExecutionPolicy(transport="bus")
    with pytest.raises(ValueError):
        ExecutionPolicy(autotune="guess")


def test_policy_autotune_scopes_the_run(monkeypatch):
    """ExecutionPolicy.autotune drives ops autotune mode for the run and
    restores the previous mode afterwards; the NTX_AUTOTUNE env var stays
    honored as the deprecated fallback."""
    monkeypatch.delenv("NTX_AUTOTUNE", raising=False)
    assert ops.get_autotune_mode() == "model"
    monkeypatch.setenv("NTX_AUTOTUNE", "measure")
    assert ops.get_autotune_mode() == "measure"   # env fallback
    seen = {}
    orig = CommandStream.execute

    def spy(self, mem):
        seen["mode"] = ops.get_autotune_mode()
        return orig(self, mem)

    monkeypatch.setattr(CommandStream, "execute", spy)
    p, x, y, *_ = _chain_program(32)
    ex = Executor(policy="fused", autotune="model")
    ex.run(p, inputs={x: _arr(32), y: _arr(32)})
    assert seen["mode"] == "model"                # policy overrode env
    assert ops.get_autotune_mode() == "measure"   # restored after the run


def test_policy_backend_scopes_the_run(monkeypatch):
    seen = {}
    orig = CommandStream.execute

    def spy(self, mem):
        seen["backend"] = ops.get_backend()
        return orig(self, mem)

    monkeypatch.setattr(CommandStream, "execute", spy)
    p, x, y, *_ = _chain_program(32)
    prev = ops.get_backend()
    Executor(policy="fused", backend="pallas_interpret").run(
        p, inputs={x: _arr(32), y: _arr(32)})
    assert seen["backend"] == "pallas_interpret"
    assert ops.get_backend() == prev


# ----------------------------------------------------------------------
# The old dispatch_* shims are gone; run_descriptors is the raw layer
# ----------------------------------------------------------------------
def test_dispatch_shims_removed():
    """The PR-4 deprecation ran its course: the shims no longer exist
    anywhere in the public surface."""
    import repro.core
    import repro.core.dispatch
    for mod in (repro.core, repro.core.dispatch):
        assert not hasattr(mod, "dispatch_stream")
        assert not hasattr(mod, "dispatch_graph")
    assert "dispatch_stream" not in repro.core.__all__
    assert "dispatch_graph" not in repro.core.__all__


def test_run_descriptors_matches_run_per_policy():
    """The raw-descriptor layer the shims used to wrap is bit-equal to
    the Program front door under every forced policy."""
    p, x, y, *_ = _chain_program(128)
    inputs = {x: _arr(128), y: _arr(128)}
    mem = p.pack(inputs)
    descs = p.descriptors
    for pol in ("fused", "multistream", "pipeline"):
        via_raw = Executor().run_descriptors(descs, mem, policy=pol)
        want = np.asarray(Executor(policy=pol).run(p, inputs=inputs).mem)
        np.testing.assert_array_equal(np.asarray(via_raw), want,
                                      err_msg=pol)


# ----------------------------------------------------------------------
# ARGMAX / ARGMIN chain tails (the open ROADMAP item)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("red", ["argmax", "argmin"])
def test_arg_chain_tail_fuses_and_matches_dispatch(red):
    """chain -> ARGMAX/ARGMIN fuses into one FusedChainReduce pass whose
    index write-back equals folding per-descriptor dispatch."""
    n = 300
    p = Program()
    x = p.buffer((n,), name="x", init=_arr(n))
    t = p.thresh(x, -0.5)
    p.relu(t, out=t)
    s = p.reduce(red, t)
    groups = plan_stream(p.descriptors)
    assert len(groups) == 1
    assert isinstance(groups[0], FusedChainReduce)
    assert groups[0].red_op == red
    mem = p.pack()
    fused = CommandStream(p.descriptors).execute(mem)
    seq = mem
    for d in p.descriptors:
        seq = dispatch(d, seq)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))
    res = p.unpack(fused)
    want = (np.argmax if red == "argmax" else np.argmin)(res[t])
    assert int(res[s][0]) == int(want)


@pytest.mark.parametrize("red", ["argmax", "argmin"])
def test_chain_reduce_arg_tails_pallas_matches_ref(red):
    """ops.chain_reduce arg tails: Pallas (interpret) == ref, first-wins
    tie behaviour included (the comparator + index-counter datapath)."""
    x = RNG.standard_normal((3, 700)).astype(np.float32)
    x[1, 13] = x[1, 600] = x[1].max() + 5.0      # tie inside one row
    x[2, 100] = x[2, 101] = x[2].min() - 5.0
    stages = [("thresh", -10.0)]
    out_r, red_r = ops.chain_reduce(stages, red, x)
    with ops.backend("pallas_interpret"):
        out_p, red_p = ops.chain_reduce(stages, red, x)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(red_p), np.asarray(red_r))
    fn = np.argmax if red == "argmax" else np.argmin
    np.testing.assert_array_equal(np.asarray(red_r),
                                  fn(np.asarray(out_r), axis=-1))


def test_program_arg_reductions_bit_equal_across_policies():
    """The satellite end-to-end: Program-built sampling tails stay
    bit-equal under every policy (index datapath through the mesh)."""
    n = 200
    p = Program()
    rows = []
    for i in range(4):
        r = p.buffer((n,), name=f"r{i}", init=_arr(n))
        t = p.thresh(r, 0.0)
        p.reduce("argmax", t, name=f"amax{i}")
        p.reduce("argmin", t, name=f"amin{i}")
        rows.append(r)
    base = np.asarray(Executor(policy="serial").run(p).mem)
    for pol in ("fused", "multistream", "pipeline"):
        got = np.asarray(Executor(policy=pol).run(p).mem)
        np.testing.assert_array_equal(got, base, err_msg=pol)


# ----------------------------------------------------------------------
# Handoff-aware stage LPT
# ----------------------------------------------------------------------
def _producer_consumer_program(n_lanes=4, n=64):
    p = Program()
    for i in range(n_lanes):
        x = p.buffer((n,), name=f"x{i}", init=np.ones(n, np.float32))
        t = p.thresh(x, 0.1)
        u = p.relu(t)
        p.copy(u)
    return p


def test_stage_lpt_colocates_consumers_with_producers():
    """Consumer nodes land on their producer's cluster: every handoff
    prices to zero cross-cluster DMA while the stage stays LPT-balanced
    (the ROADMAP handoff-aware-LPT item)."""
    p = _producer_consumer_program(n_lanes=4)
    ss = StageSchedule(p.descriptors, n_clusters=4)
    assert ss.stats["n_stages"] == 3
    assert ss.stats["handoff_bytes"] > 0
    assert ss.stats["handoff_bytes_cross"] == 0
    for h in ss.handoffs:
        assert not h["cross_cluster"]
    # balance not sacrificed: the 4 equal-cost lanes still spread
    for stage in ss.stages:
        assert len({ss.assignment[i] for i in stage}) == len(stage)


def test_stage_lpt_balance_beats_affinity_when_dma_is_cheap():
    """One big producer feeding many consumers: co-locating ALL consumers
    would serialize the stage; the LPT term must still spread them (the
    affinity bias is a price, not a constraint)."""
    n = 64
    p = Program()
    src = p.buffer((n,), name="src", init=np.ones(n, np.float32))
    t = p.thresh(src, 0.0)          # single producer node
    for i in range(4):
        p.relu(t)                   # 4 equal consumers of t
    ss = StageSchedule(p.descriptors, n_clusters=4)
    consumer_stage = ss.stages[-1]
    assert len(consumer_stage) == 4
    # all-on-one-cluster would make the stage critical path 4x one node;
    # the assignment must use more than one cluster
    assert len({ss.assignment[i] for i in consumer_stage}) > 1
    got = np.asarray(ss.execute(p.pack()))
    want = np.asarray(CommandStream(p.descriptors).execute(p.pack()))
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------------------
# The ntx front door
# ----------------------------------------------------------------------
def test_ntx_namespace_reexports_core():
    assert ntx.Program is Program
    assert ntx.Executor is Executor
    assert ntx.ExecutionPolicy is ExecutionPolicy
    with ntx.Program() as p:
        x = p.buffer((8,), name="x", init=np.arange(8, dtype=np.float32))
        y = p.relu(x)
    res = ntx.Executor().run(p)
    np.testing.assert_array_equal(res[y], np.arange(8))


def test_executor_plan_cache_reused_across_runs():
    """Steady-state loops must not replan: the Executor caches the
    resolved policy + runner on the program, keyed by its version —
    and evicts plans for superseded versions (they can never be hit)."""
    p, x, y, *_ = _chain_program(64)
    ex = Executor()
    ex.run(p, inputs={x: _arr(64), y: _arr(64)})
    cache_keys = set(p._plan_cache)
    ex.run(p, inputs={x: _arr(64), y: _arr(64)})
    assert set(p._plan_cache) == cache_keys
    # mutating the program invalidates: new version planned, stale evicted
    p.relu(y)
    ex.run(p, inputs={x: _arr(64), y: _arr(64)})
    assert set(p._plan_cache).isdisjoint(cache_keys)
    assert all(k[0] == p.version for k in p._plan_cache)


def test_executor_plan_cache_keyed_by_backend_and_autotune():
    """A jitted transport bakes the kernel backend in at trace time: two
    executors differing only in backend/autotune must not share a plan."""
    p, x, y, *_ = _chain_program(64)
    inputs = {x: _arr(64), y: _arr(64)}
    a = Executor(policy="multistream", transport="vmap")
    b = Executor(policy="multistream", transport="vmap",
                 backend="pallas_interpret")
    c = Executor(policy="multistream", transport="vmap",
                 autotune="measure")
    r1 = np.asarray(a.run(p, inputs=inputs).mem)
    r2 = np.asarray(b.run(p, inputs=inputs).mem)
    c.run(p, inputs=inputs)
    assert len(p._plan_cache) == 3
    np.testing.assert_allclose(r1, r2, rtol=1e-6, atol=1e-6)
