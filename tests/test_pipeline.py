"""Stage-pipelined dependent sub-streams (core/multistream.StageSchedule)
plus the correctness sweep riding along: AGU span analysis on degenerate
nests, the autotune cache key, perfmodel gain-ratio guards, and LPT
partition validity.

Every pipelined execute mode must stay bit-equivalent to serial
CommandStream execution (and, with tolerance, to folding the dispatch
oracle), on crafted uniform pipelines, random dependent DAGs and the
runtime wiring.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (Agu, ClusterScheduler, CommandStream, Descriptor,
                        Executor, Opcode, StageSchedule, StreamGraph,
                        dispatch, gemm, memcpy, memset, relu)


def dispatch_graph(descs, mem, n_clusters=None, mode="auto",
                   pipeline=False):
    """The old one-call facade, retargeted at the Executor front door
    (the deprecated shim was removed)."""
    return Executor(n_clusters=n_clusters, transport=mode).run_descriptors(
        descs, mem, policy="pipeline" if pipeline else "multistream")
from repro.core.multistream import _lpt_assign
from repro.core.stream import agu_span, program_spans, spans_overlap

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(11)


def _mem(n=1 << 14):
    return RNG.standard_normal(n).astype(np.float32)


def _ew(op, n, src, dst, imm=0.0, y=None):
    return Descriptor(bounds=(n,), opcode=op, imm=imm,
                      agu0=Agu(src, (1,)),
                      agu1=Agu(y, (1,)) if y is not None else Agu(),
                      agu2=Agu(dst, (1,)))


def _producer_consumer(n_lanes=4, n=256, lane=2048):
    """n_lanes dependent chains: producer writes t, consumer reads t
    (the RAW handoff) and writes u. Uniform across lanes."""
    descs = []
    for i in range(n_lanes):
        x, t, u = lane * i, lane * i + n, lane * i + 2 * n
        descs += [_ew(Opcode.THRESH, n, x, t, imm=0.2),
                  _ew(Opcode.RELU, n, t, t),
                  _ew(Opcode.THRESH, n, t, u, imm=0.1),
                  _ew(Opcode.RELU, n, u, u)]
    return descs


# ----------------------------------------------------------------------
# Tentpole: stage schedule structure
# ----------------------------------------------------------------------
def test_dependent_chain_levelizes_not_serializes():
    """The unlock: ClusterScheduler collapses a dependent chain to ONE
    component; StageSchedule keeps the RAW edges and level-izes."""
    descs = _producer_consumer(n_lanes=4)
    comp = ClusterScheduler(descs, n_clusters=4)
    assert comp.stats["n_substreams"] == 4          # lane = one component
    ss = StageSchedule(descs, n_clusters=4)
    assert ss.stats["n_nodes"] == 8                 # producer + consumer
    assert ss.stats["n_stages"] == 2
    assert ss.stats["stage_sizes"] == [4, 4]
    assert sorted(ss.level) == [0, 0, 0, 0, 1, 1, 1, 1]
    # both stages are uniform across lanes -> stacked transports legal
    for stage in ss.stages:
        assert ss.plan_stage_mode(stage, "vmap") == "vmap"


def test_pipeline_handoff_sizing():
    """A handoff is the producer's write span inside the consumer's
    rebased window: 4 bytes/elem * n per lane here."""
    n = 256
    descs = _producer_consumer(n_lanes=2, n=n)
    ss = StageSchedule(descs, n_clusters=2)
    assert len(ss.handoffs) == 2
    for h in ss.handoffs:
        assert h["bytes"] == 4 * n
        assert h["stage"] == 1
    assert ss.stats["handoff_bytes"] == 2 * 4 * n


def test_pipeline_modes_bit_equal_to_serial():
    descs = _producer_consumer(n_lanes=4)
    mem = _mem()
    want = np.asarray(CommandStream(descs).execute(mem))
    for mode in ("auto", "interleave", "vmap", "shard_map"):
        got = np.asarray(
            StageSchedule(descs, n_clusters=4).execute(mem, mode))
        np.testing.assert_array_equal(want, got, err_msg=mode)
    got = np.asarray(dispatch_graph(descs, mem, pipeline=True))
    np.testing.assert_array_equal(want, got)


def test_pipeline_three_stage_chain():
    """A 3-deep dependent chain levels into 3 stages and still matches."""
    n = 128
    descs = []
    for i in range(3):
        base = 4096 * i
        a, b, c, d = base, base + 512, base + 1024, base + 1536
        descs += [_ew(Opcode.RELU, n, a, b),
                  _ew(Opcode.THRESH, n, b, c, imm=0.1),
                  _ew(Opcode.AXPY, n, c, d, imm=2.0, y=a)]
    ss = StageSchedule(descs, n_clusters=3)
    assert ss.stats["n_stages"] == 3
    mem = _mem()
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(ss.execute(mem, "vmap")))


def test_pipeline_scc_merges_write_pingpong():
    """R1 -> R2 -> back into R1: the node cycle must condense into ONE
    node (serial inside), not deadlock or mis-order."""
    n = 64
    descs = [_ew(Opcode.RELU, n, 0, 1024),            # writes R1
             _ew(Opcode.THRESH, n, 1024, 2048, imm=0.1),  # R1 -> R2
             _ew(Opcode.AXPY, n, 2048, 1024, imm=0.5, y=2048)]  # R2 -> R1
    ss = StageSchedule(descs, n_clusters=2)
    assert ss.stats["n_nodes"] == 1
    assert ss.stats["n_stages"] == 1
    mem = _mem()
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(ss.execute(mem)))


def test_independent_program_is_single_stage():
    """No edges -> one stage; StageSchedule degrades to the concurrent
    independent case and still matches serial."""
    descs = [_ew(Opcode.RELU, 128, 4096 * i, 4096 * i + 512)
             for i in range(3)]
    ss = StageSchedule(descs, n_clusters=3)
    assert ss.stats["n_stages"] == 1 and ss.stats["n_nodes"] == 3
    mem = _mem()
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(ss.execute(mem)))


def test_stage_mode_fallback_non_uniform():
    """A stage mixing different node programs falls back to interleave
    (per-stage), and execution still matches serial."""
    n = 128
    descs = _producer_consumer(n_lanes=2, n=n)
    descs.append(memset(32, 1.5, 12000))            # breaks uniformity
    ss = StageSchedule(descs, n_clusters=2)
    modes = [ss.plan_stage_mode(s, "vmap") for s in ss.stages]
    assert "interleave" in modes
    mem = _mem()
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(ss.execute(mem, "vmap")))


def test_pipeline_model_speedup_and_gain():
    from repro.perfmodel.ntx import pipeline_gain
    descs = _producer_consumer(n_lanes=4)
    g = pipeline_gain(descs, n_clusters=4)
    assert g["n_stages"] == 2.0 and g["n_nodes"] == 8.0
    assert g["speedup"] > 1.0
    assert np.isfinite(g["speedup"])
    ss = StageSchedule(descs, n_clusters=4)
    assert ss.model_speedup() == pytest.approx(g["speedup"], rel=1e-9)
    # pipelined time can never beat one-node-per-cluster-per-stage
    assert g["time_pipeline_s"] >= max(ss.costs)


# ----------------------------------------------------------------------
# Property test: random dependent DAGs, pipeline == serial
# ----------------------------------------------------------------------
def _random_dep_program(rng) -> list:
    """Random program over a few shared regions so RAW/WAR/WAW chains are
    common; includes memset/reductions/GEMMs and zero-trip descriptors."""
    descs = []
    reg = lambda i: int(i) * 1024
    for _ in range(rng.integers(3, 10)):
        kind = rng.integers(0, 6)
        n = int(rng.integers(8, 200))
        src = reg(rng.integers(0, 8))
        dst = reg(rng.integers(0, 8))
        if kind == 0:
            descs.append(_ew(rng.choice([Opcode.RELU, Opcode.THRESH,
                                         Opcode.COPY]), n, src, dst,
                             imm=float(rng.standard_normal())))
        elif kind == 1:
            descs.append(_ew(rng.choice([Opcode.ADD, Opcode.MUL,
                                         Opcode.AXPY, Opcode.SUB]),
                             n, src, dst, imm=1.5, y=reg(rng.integers(0, 8))))
        elif kind == 2:
            descs.append(memset(int(rng.integers(8, 128)),
                                float(rng.standard_normal()), dst))
        elif kind == 3:
            from repro.core import argmax
            descs.append(argmax(int(rng.integers(8, 128)), src,
                                reg(rng.integers(12, 15))))
        elif kind == 4:
            m = int(rng.integers(2, 9))
            descs.append(gemm(m, m, m, src, src + 256, src + 512))
        else:
            descs.append(Descriptor(bounds=(0,), opcode=Opcode.RELU,
                                    agu0=Agu(src, (1,)),
                                    agu2=Agu(dst, (1,))))
    return descs


def test_random_dependent_dags_pipeline_matches_serial():
    """Deterministic stand-in for the hypothesis property: across random
    dependent DAGs, every pipelined mode == serial CommandStream (and the
    dispatch-fold oracle within kernel tolerance)."""
    for seed in range(25):
        rng = np.random.default_rng(seed)
        descs = _random_dep_program(rng)
        mem = rng.standard_normal(1 << 14).astype(np.float32)
        want = np.asarray(CommandStream(descs).execute(mem))
        oracle = jnp.asarray(mem)
        for d in descs:
            oracle = dispatch(d, oracle)
        np.testing.assert_allclose(want, np.asarray(oracle),
                                   rtol=1e-5, atol=1e-5)
        for mode in ("auto", "interleave", "vmap"):
            got = np.asarray(StageSchedule(descs, n_clusters=3)
                             .execute(mem, mode))
            np.testing.assert_allclose(want, got, rtol=1e-5, atol=1e-5,
                                       err_msg=f"seed {seed} mode {mode}")


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_property_random_dependent_dags(seed):
        rng = np.random.default_rng(seed)
        descs = _random_dep_program(rng)
        mem = rng.standard_normal(1 << 14).astype(np.float32)
        want = np.asarray(CommandStream(descs).execute(mem))
        got = np.asarray(dispatch_graph(descs, mem, n_clusters=3,
                                        pipeline=True))
        np.testing.assert_allclose(want, got, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# Multi-device shard_map path (subprocess, 8 emulated devices)
# ----------------------------------------------------------------------
def test_pipeline_shard_map_on_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core import Agu, CommandStream, Descriptor, Opcode
        from repro.core.multistream import StageSchedule
        rng = np.random.default_rng(0)
        n = 2048
        descs = []
        for i in range(4):
            x, t, u = 8 * n * i, 8 * n * i + n, 8 * n * i + 2 * n
            descs += [Descriptor(bounds=(n,), opcode=Opcode.THRESH, imm=0.2,
                                 agu0=Agu(x, (1,)), agu2=Agu(t, (1,))),
                      Descriptor(bounds=(n,), opcode=Opcode.RELU,
                                 agu0=Agu(t, (1,)), agu2=Agu(u, (1,)))]
        mem = jnp.asarray(rng.standard_normal(32 * n).astype(np.float32))
        sched = StageSchedule(descs, n_clusters=4)
        got = np.asarray(sched.execute(mem, mode="shard_map"))
        want = np.asarray(CommandStream(descs).execute(mem))
        print(json.dumps({
            "n_devices": len(jax.devices()),
            "n_stages": sched.stats["n_stages"],
            "stage_modes": sched.stats["stage_modes"],
            "n_used": sched.stats.get("n_devices_used"),
            "equal": bool((got == want).all())}))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["n_devices"] == 8
    assert r["n_stages"] == 2
    assert r["stage_modes"] == ["shard_map", "shard_map"]
    assert r["n_used"] == 4            # one device per lane per stage
    assert r["equal"]


# ----------------------------------------------------------------------
# Satellite: AGU span analysis on degenerate nests
# ----------------------------------------------------------------------
def test_agu_span_zero_trip_is_empty():
    """b == 0 must yield an empty span, not shrink lo below base (the
    pre-fix stride * (b - 1) folding) or overstate hi."""
    assert agu_span(Agu(100, (4,)), (0,)) == (100, 100)
    assert agu_span(Agu(100, (-4,)), (0,)) == (100, 100)
    assert agu_span(Agu(100, (1, 8)), (16, 0)) == (100, 100)


def test_agu_span_zero_stride_and_singleton():
    assert agu_span(Agu(100, (0,)), (5,)) == (100, 101)     # one address
    assert agu_span(Agu(100, (7,)), (1,)) == (100, 101)     # single trip
    assert agu_span(Agu(100, (-2,)), (3,)) == (96, 101)     # negative walks down


def test_empty_spans_never_overlap():
    assert not spans_overlap((100, 100), (0, 1000))
    assert not spans_overlap((0, 1000), (100, 100))
    assert not spans_overlap((100, 100), (100, 100))


def test_zero_trip_descriptor_conflicts_with_nothing():
    """Regression: a zero-trip COPY at base 50 used to span (49, 51) and
    manufacture phantom edges against anything touching those addresses."""
    z = Descriptor(bounds=(0,), opcode=Opcode.COPY,
                   agu0=Agu(0, (1,)), agu2=Agu(50, (1,)))
    others = [relu(64, 0, 32),                  # writes [32, 96)
              memcpy(64, 40, 3000)]             # reads  [40, 104)
    g = StreamGraph([others[0], z, others[1]])
    assert g.n_edges == 1                       # only relu -> memcpy (RAW)
    assert all(z not in (s.descs if len(s.descs) > 1 else [])
               for s in g.partition())
    assert len(g.partition()) == 2              # z is its own component
    # execution: a zero-trip command is a no-op everywhere
    mem = _mem(4096)
    np.testing.assert_array_equal(np.asarray(dispatch(z, mem)), mem)
    np.testing.assert_array_equal(
        np.asarray(CommandStream([z]).execute(mem)), mem)
    from repro.core import execute, execute_vectorized
    np.testing.assert_array_equal(execute(z, mem), mem)
    np.testing.assert_array_equal(execute_vectorized(z, mem), mem)
    # and the full program still matches serial under the graph scheduler
    descs = [others[0], z, others[1]]
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(dispatch_graph(descs, mem, pipeline=True)))


def test_zero_trip_gemm_does_not_fuse_epilogue():
    """Regression: a k=0 (zero-trip) MAC in canonical GEMM form followed
    by a streaming op on C must NOT fuse into a GEMM+epilogue — the MAC
    is a no-op, so C keeps its old contents and only the epilogue op
    applies (matching the dispatch fold)."""
    m = n = 4
    g = Descriptor(bounds=(0, n, m), opcode=Opcode.MAC,
                   init_level=1, store_level=1,
                   agu0=Agu(0, (1, 0, 0)), agu1=Agu(64, (n, 1, 0)),
                   agu2=Agu(128, (0, 1, n)))
    ep = relu(m * n, 128, 128)
    descs = [g, ep]
    mem = _mem(1024)
    oracle = jnp.asarray(mem)
    for d in descs:
        oracle = dispatch(d, oracle)
    got = np.asarray(CommandStream(descs).execute(mem))
    np.testing.assert_array_equal(np.asarray(oracle), got)
    np.testing.assert_array_equal(np.maximum(mem[128:128 + m * n], 0.0),
                                  got[128:128 + m * n])


def test_handoff_sized_by_read_footprint_not_window_hull():
    """A producer write the consumer never reads — even one inside the
    consumer's window hull — must not count as handoff bytes."""
    n = 64
    descs = [_ew(Opcode.RELU, n, 0, 1024),          # producer writes A
             _ew(Opcode.RELU, n, 0, 4096),          # producer writes B
             # consumer reads A and a far region, never B — but B falls
             # inside the consumer window hull [1024, 8192 + n)
             _ew(Opcode.ADD, n, 1024, 8192, y=6144)]
    ss = StageSchedule(descs, n_clusters=2)
    handoff = {(h["src"], h["dst"]): h["bytes"] for h in ss.handoffs}
    nodes_writing = {nd.write_ranges[0][0]: i
                     for i, nd in enumerate(ss.nodes) if nd.write_ranges}
    a_node, b_node = nodes_writing[1024], nodes_writing[4096]
    c_node = nodes_writing[8192]
    assert handoff[(a_node, c_node)] == 4 * n       # A is read: counted
    assert (b_node, c_node) not in handoff          # B: no edge at all
    assert ss.stats["handoff_bytes"] == 4 * n


def test_program_spans_export():
    n = 64
    descs = [_ew(Opcode.RELU, n, 0, 256),
             _ew(Opcode.ADD, n, 256, 512, y=1024)]
    reads, writes = program_spans(descs)
    assert reads == [(0, n), (256, 256 + n), (1024, 1024 + n)]
    assert writes == [(256, 256 + n), (512, 512 + n)]
    cs = CommandStream(descs)
    assert cs.read_spans() == reads and cs.write_spans() == writes


# ----------------------------------------------------------------------
# Satellite: autotune cache key (backend + NTX_AUTOTUNE mode)
# ----------------------------------------------------------------------
def test_autotune_cache_keyed_by_backend_and_mode(monkeypatch):
    """A cache warmed under ref/model must NOT be served after switching
    to measure/Pallas: flipping the env var re-tunes."""
    from repro.kernels import ops
    ops.clear_autotune_cache()
    monkeypatch.setenv("NTX_AUTOTUNE", "model")
    with ops.backend("ref"):
        ops.matmul_blocks(32, 40, 24)
    st0 = ops.block_cache_stats()
    assert st0["misses"] == 1 and st0["measured"] == 0
    monkeypatch.setenv("NTX_AUTOTUNE", "measure")
    with ops.backend("pallas_interpret"):
        blocks = ops.matmul_blocks(32, 40, 24)
    st1 = ops.block_cache_stats()
    assert st1["misses"] == st0["misses"] + 1   # stale entry not served
    assert st1["measured"] == 1                 # measured racing ran
    with ops.backend("pallas_interpret"):       # same key: hit, no re-race
        assert ops.matmul_blocks(32, 40, 24) == blocks
    st2 = ops.block_cache_stats()
    assert st2["hits"] == st1["hits"] + 1 and st2["measured"] == 1
    ops.clear_autotune_cache()
    assert ops.block_cache_stats() == {"hits": 0, "misses": 0,
                                       "measured": 0, "refused": 0}


def test_autotune_cache_keyed_by_dtype_bytes():
    from repro.kernels import ops
    ops.clear_autotune_cache()
    ops.matmul_blocks(512, 512, 512, dtype_bytes=4)
    ops.matmul_blocks(512, 512, 512, dtype_bytes=2)
    assert ops.block_cache_stats()["misses"] == 2


# ----------------------------------------------------------------------
# Satellite: perfmodel gain-ratio guards
# ----------------------------------------------------------------------
def test_gain_ratios_guarded_on_degenerate_programs():
    """Empty program and single zero-cost (zero-trip) descriptor: every
    gain ratio is exactly 1.0 — no ZeroDivisionError, no inf/nan."""
    from repro.perfmodel.ntx import (multistream_gain, pipeline_gain,
                                     stream_fusion_gain)
    zero_trip = Descriptor(bounds=(0,), opcode=Opcode.RELU,
                           agu0=Agu(0, (1,)), agu2=Agu(0, (1,)))
    for descs in ([], [zero_trip]):
        f = stream_fusion_gain(descs, setup_cycles=0)
        m = multistream_gain(descs, n_clusters=4, setup_cycles=0)
        p = pipeline_gain(descs, n_clusters=4, setup_cycles=0)
        assert f["speedup"] == 1.0
        assert m["speedup"] == 1.0 and m["dma_overlap_gain"] == 1.0
        assert p["speedup"] == 1.0
        for g in (f, m, p):
            for v in g.values():
                if isinstance(v, float):
                    assert np.isfinite(v), (g, v)


# ----------------------------------------------------------------------
# Satellite: LPT partition validity
# ----------------------------------------------------------------------
def test_lpt_assign_valid_partition_property():
    """Random costs x cluster counts (clusters > streams, zero costs,
    empty lists): always a valid partition, never an IndexError."""
    rng = np.random.default_rng(0)
    for _ in range(300):
        n = int(rng.integers(0, 12))
        costs = [float(c) for c in rng.choice([0.0, 0.5, 1.0, 3.0], n)]
        k = int(rng.integers(1, 10))
        assign = _lpt_assign(costs, k)
        assert len(assign) == len(costs)
        assert all(0 <= c < k for c in assign)
        load = [0.0] * k
        for c, a in zip(costs, assign):
            load[a] += c
        assert sum(load) == pytest.approx(sum(costs))
    assert _lpt_assign([1.0], 0) == [0]          # clamps, no crash
    assert _lpt_assign([], 5) == []


def test_scheduler_more_clusters_than_substreams():
    descs = [_ew(Opcode.RELU, 64, 4096 * i, 4096 * i + 512)
             for i in range(2)]
    sched = ClusterScheduler(descs, n_clusters=16)
    times = sched.cluster_times()
    assert len(times) == 16 and sum(1 for t in times if t > 0) == 2
    s = sched.model_speedup()
    assert np.isfinite(s) and s >= 1.0
    mem = _mem()
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(sched.execute(mem)))
    ss = StageSchedule(descs, n_clusters=16)
    assert np.isfinite(ss.model_speedup())
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(ss.execute(mem)))


def test_scheduler_all_zero_costs():
    """Zero-trip-only program: zero costs everywhere, still a valid
    partition and finite (1.0) speedups."""
    descs = [Descriptor(bounds=(0,), opcode=Opcode.RELU,
                        agu0=Agu(64 * i, (1,)), agu2=Agu(64 * i, (1,)))
             for i in range(3)]
    sched = ClusterScheduler(descs, n_clusters=5, setup_cycles=0)
    assert sched.model_speedup() == 1.0
    mem = _mem(1024)
    np.testing.assert_array_equal(np.asarray(sched.execute(mem)), mem)


if HAVE_HYPOTHESIS:
    @given(st.lists(st.floats(0.0, 100.0), max_size=16),
           st.integers(1, 12))
    @settings(max_examples=100, deadline=None)
    def test_property_lpt_partition(costs, k):
        assign = _lpt_assign(costs, k)
        assert len(assign) == len(costs)
        assert all(0 <= c < k for c in assign)


# ----------------------------------------------------------------------
# Runtime wiring
# ----------------------------------------------------------------------
def test_serve_prefill_pipelined_argmax():
    from repro.runtime.serve import (greedy_argmax_pipelined,
                                     _PREFILL_PROGRAMS)
    logits = RNG.standard_normal((6, 500)).astype(np.float32)
    np.testing.assert_array_equal(greedy_argmax_pipelined(logits),
                                  logits.argmax(-1))
    tied = np.zeros((2, 7), np.float32)
    tied[0, 3] = tied[0, 5] = 2.0
    np.testing.assert_array_equal(greedy_argmax_pipelined(tied),
                                  tied.argmax(-1))
    # the sampler is a Program run through the pipeline policy: the
    # Executor's StageSchedule level-izes COPY -> ARGMAX chains into a
    # head stage and a sampler stage
    _, executor, _, _ = _PREFILL_PROGRAMS[(6, 500)]
    assert executor.stats["policy"] == "pipeline"
    assert executor.stats["scheduler"]["n_stages"] == 2


def test_train_update_plan_pipelined():
    from repro.runtime.train import plan_update_multistream
    params = {"l0": {"w": np.zeros((64, 64)), "b": np.zeros((64,))},
              "l1": {"w": np.zeros((64, 64))}}
    plan = plan_update_multistream(params, n_clusters=2)
    assert plan["n_substreams"] == 3            # one component per tensor
    pp = plan["pipeline"]
    assert pp["n_nodes"] == 6                   # precondition + apply
    assert pp["n_stages"] == 2
    assert pp["model_speedup"] > 1.0
    assert pp["handoff_bytes"] > 0


# ----------------------------------------------------------------------
# Benchmark CI smoke: --json --quick and the schema bump rules
# ----------------------------------------------------------------------
def test_bench_json_quick_smoke():
    """Schema regressions fail tier-1 instead of silently drifting.
    Bump rules: schema_version changes ONLY on breaking changes (key
    removal/rename/type change); adding sections or rows keeps it at 1.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--json", "--quick", "pipeline", "multistream", "fusion"],
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    doc = json.loads(out.stdout)
    assert doc["schema_version"] == 1
    assert set(doc["sections"]) == {"pipeline", "multistream", "fusion"}
    for rows in doc["sections"].values():
        assert rows and all(set(r) == {"name", "us_per_call", "derived"}
                            for r in rows)
        assert all(isinstance(r["us_per_call"], float) for r in rows)
    by_name = {r["name"]: r["derived"]
               for r in doc["sections"]["pipeline"]}
    assert by_name["pipeline.match"] == 1
    assert by_name["pipeline.workload.n_stages"] == 2
    assert float(by_name["pipeline.model_speedup_c4"]) > 1.0
