"""Multi-cluster stream scheduling (core/multistream.py).

The partition must be provably independent (graph-vs-serial equivalence on
overlapping/disjoint span mixes — bit-identical where execution uses the
same kernels), deterministic, and load-balanced; the runtime/benchmark
wiring must route through it.
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
                        Executor, Opcode, StreamGraph, argmax, gemm,
                        memcpy, memset)


def dispatch_graph(descs, mem):
    """The old one-call facade, retargeted at the Executor front door
    (the deprecated shim was removed)."""
    return Executor().run_descriptors(descs, mem, policy="multistream")
from repro.core.multistream import _lpt_assign, desc_spans

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(7)


def _mem(n=1 << 14):
    return RNG.standard_normal(n).astype(np.float32)


def _ew(op, n, src, dst, imm=0.0, y=None):
    return Descriptor(bounds=(n,), opcode=op, imm=imm,
                      agu0=Agu(src, (1,)),
                      agu1=Agu(y, (1,)) if y is not None else Agu(),
                      agu2=Agu(dst, (1,)))


def _chain(base, n=256, t_off=512):
    """A 3-op in-place chain reading [base, base+n), writing t = base+t_off."""
    t = base + t_off
    return [_ew(Opcode.THRESH, n, base, t, imm=0.2),
            _ew(Opcode.RELU, n, t, t),
            _ew(Opcode.THRESH, n, t, t, imm=0.5)]


# ----------------------------------------------------------------------
# Partitioning
# ----------------------------------------------------------------------
def test_disjoint_spans_partition_and_bit_identity():
    """A program of 4 disjoint chains partitions into 4 concurrent
    sub-streams whose graph execution is BIT-identical to serial
    CommandStream.execute (the acceptance property)."""
    descs = sum((_chain(i * 1024) for i in range(4)), [])
    g = StreamGraph(descs)
    subs = g.partition()
    assert len(subs) >= 2
    assert [s.indices for s in subs] == [(0, 1, 2), (3, 4, 5),
                                         (6, 7, 8), (9, 10, 11)]
    mem = _mem()
    serial = np.asarray(CommandStream(descs).execute(mem))
    for mode in ("auto", "interleave", "vmap"):
        got = np.asarray(ClusterScheduler(g, n_clusters=4).execute(mem, mode))
        np.testing.assert_array_equal(serial, got, err_msg=mode)


def test_overlapping_spans_single_component():
    """RAW/WAR/WAW overlaps force one component; execution still matches."""
    n = 128
    descs = [_ew(Opcode.RELU, n, 0, 1024),          # writes T1
             _ew(Opcode.THRESH, n, 1024, 2048, imm=0.1),   # RAW on T1
             _ew(Opcode.COPY, n, 3000, 1024 + n // 2)]     # WAW overlap T1
    g = StreamGraph(descs)
    assert len(g.partition()) == 1
    mem = _mem()
    got = np.asarray(dispatch_graph(descs, mem))
    want = np.asarray(CommandStream(descs).execute(mem))
    np.testing.assert_array_equal(want, got)


def test_mixed_overlap_disjoint_spans():
    """A mix: two dependent commands + one disjoint chain -> 2 components,
    graph == serial."""
    n = 128
    descs = [_ew(Opcode.RELU, n, 0, 1024),
             _ew(Opcode.THRESH, n, 1024, 1024, imm=0.2),   # same T: chain
             _ew(Opcode.RELU, n, 4096, 5120),              # disjoint
             _ew(Opcode.THRESH, n, 5120, 5120, imm=0.3)]
    g = StreamGraph(descs)
    subs = g.partition()
    assert [s.indices for s in subs] == [(0, 1), (2, 3)]
    mem = _mem()
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(dispatch_graph(descs, mem)))


def test_read_sharing_stays_independent():
    """Two streams reading the SAME region (shared weights) but writing
    disjoint regions are independent — read-read creates no edge."""
    n = 128
    descs = [_ew(Opcode.AXPY, n, 0, 1024, imm=2.0, y=512),
             _ew(Opcode.AXPY, n, 0, 2048, imm=3.0, y=512)]
    g = StreamGraph(descs)
    assert g.n_edges == 0
    assert len(g.partition()) == 2
    mem = _mem()
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs).execute(mem)),
        np.asarray(dispatch_graph(descs, mem)))


def test_partition_deterministic_order():
    """Interleaved independent streams partition by first-descriptor index,
    preserving program order inside each sub-stream — and repeated planning
    is identical."""
    n = 64
    a = [_ew(Opcode.RELU, n, 0, 1024), _ew(Opcode.THRESH, n, 1024, 1024,
                                           imm=0.1)]
    b = [_ew(Opcode.RELU, n, 4096, 5120), _ew(Opcode.THRESH, n, 5120, 5120,
                                              imm=0.2)]
    descs = [a[0], b[0], a[1], b[1]]
    subs1 = StreamGraph(descs).partition()
    subs2 = StreamGraph(descs).partition()
    assert [s.indices for s in subs1] == [(0, 2), (1, 3)]
    assert [s.indices for s in subs1] == [s.indices for s in subs2]
    assert [s.local for s in subs1] == [s.local for s in subs2]


def test_uniform_detection_and_stacked_modes():
    """Shifted-identical sub-streams are uniform (vmap/shard_map legal);
    a structurally different sub-stream breaks uniformity and auto falls
    back to interleaved host execution."""
    descs = sum((_chain(i * 1024) for i in range(3)), [])
    sched = ClusterScheduler(descs, n_clusters=2)
    assert sched.uniform() and sched.traceable()
    descs2 = descs + [memset(32, 1.5, 8192)]
    sched2 = ClusterScheduler(descs2, n_clusters=2)
    assert not sched2.uniform()
    assert sched2.plan_mode() == "interleave"
    mem = _mem()
    np.testing.assert_array_equal(
        np.asarray(CommandStream(descs2).execute(mem)),
        np.asarray(sched2.execute(mem)))
    with pytest.raises(ValueError):
        sched2.execute(mem, mode="vmap")


def test_gemm_streams_partition_and_match():
    """Independent GEMM+epilogue programs across the mesh: partition finds
    them, execution matches serial within kernel tolerance."""
    m = 16
    sz = m * m
    descs = []
    for i in range(3):
        base = 4 * sz * i
        descs += [gemm(m, m, m, base, base + sz, base + 2 * sz),
                  _ew(Opcode.RELU, sz, base + 2 * sz, base + 2 * sz)]
    g = StreamGraph(descs)
    assert len(g.partition()) == 3
    mem = _mem()
    sched = ClusterScheduler(g, n_clusters=2)
    want = np.asarray(CommandStream(descs).execute(mem))
    for mode in ("interleave", "vmap"):
        got = np.asarray(sched.execute(mem, mode=mode))
        np.testing.assert_allclose(want, got, rtol=1e-5, atol=1e-5,
                                   err_msg=mode)


def test_lpt_load_balance():
    assign = _lpt_assign([5.0, 1.0, 1.0, 1.0, 1.0, 1.0], 2)
    assert assign[0] == 0                      # biggest first, alone
    assert assign.count(1) >= 4                # small ones pack opposite
    # deterministic
    assert assign == _lpt_assign([5.0, 1.0, 1.0, 1.0, 1.0, 1.0], 2)


def test_scheduler_stats_and_model_speedup():
    descs = sum((_chain(i * 1024) for i in range(4)), [])
    sched = ClusterScheduler(descs, n_clusters=4)
    st = sched.stats
    assert st["n_substreams"] == 4 and st["n_clusters"] == 4
    assert sorted(st["assignment"]) == [0, 1, 2, 3]
    assert sched.model_speedup() == pytest.approx(4.0, rel=1e-6)
    from repro.perfmodel.ntx import multistream_gain
    gain = multistream_gain(descs, n_clusters=2)
    assert gain["speedup"] == pytest.approx(2.0, rel=1e-6)
    assert gain["n_substreams"] == 4.0


# ----------------------------------------------------------------------
# Property test: random descriptor programs, graph == serial
# ----------------------------------------------------------------------
def _random_program(rng) -> list:
    """Random small program over a 16K arena: contiguous streaming ops,
    memset/memcpy, reductions and GEMMs at random (possibly conflicting)
    bases."""
    descs = []
    for _ in range(rng.integers(2, 8)):
        kind = rng.integers(0, 5)
        base = int(rng.integers(0, 12)) * 1024
        if kind == 0:
            descs.append(_ew(rng.choice([Opcode.RELU, Opcode.THRESH,
                                         Opcode.COPY]),
                             int(rng.integers(8, 200)), base,
                             int(rng.integers(0, 12)) * 1024,
                             imm=float(rng.standard_normal())))
        elif kind == 1:
            descs.append(_ew(rng.choice([Opcode.ADD, Opcode.MUL,
                                         Opcode.AXPY, Opcode.SUB]),
                             int(rng.integers(8, 200)), base,
                             int(rng.integers(0, 12)) * 1024,
                             imm=1.5, y=int(rng.integers(0, 12)) * 1024))
        elif kind == 2:
            descs.append(memset(int(rng.integers(8, 128)),
                                float(rng.standard_normal()), base))
        elif kind == 3:
            descs.append(argmax(int(rng.integers(8, 128)), base,
                                int(rng.integers(12, 15)) * 1024))
        else:
            m = int(rng.integers(2, 9))
            descs.append(gemm(m, m, m, base, base + 256, base + 512))
    return descs


def test_random_programs_graph_matches_serial():
    """Deterministic stand-in for the hypothesis property: across random
    programs with arbitrary span mixes, graph scheduling == serial."""
    for seed in range(25):
        rng = np.random.default_rng(seed)
        descs = _random_program(rng)
        mem = rng.standard_normal(1 << 14).astype(np.float32)
        want = np.asarray(CommandStream(descs).execute(mem))
        got = np.asarray(dispatch_graph(descs, mem))
        np.testing.assert_allclose(want, got, rtol=1e-5, atol=1e-5,
                                   err_msg=f"seed {seed}")


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_property_random_programs(seed):
        rng = np.random.default_rng(seed)
        descs = _random_program(rng)
        mem = rng.standard_normal(1 << 14).astype(np.float32)
        want = np.asarray(CommandStream(descs).execute(mem))
        got = np.asarray(dispatch_graph(descs, mem))
        np.testing.assert_allclose(want, got, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# Multi-device shard_map path (subprocess, 8 emulated devices)
# ----------------------------------------------------------------------
def test_shard_map_path_on_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core import Agu, CommandStream, Descriptor, Opcode
        from repro.core.multistream import ClusterScheduler
        rng = np.random.default_rng(0)
        n = 4096
        descs = []
        for i in range(4):
            x, t = 2 * n * i, 2 * n * i + n
            descs += [Descriptor(bounds=(n,), opcode=Opcode.THRESH, imm=0.2,
                                 agu0=Agu(x, (1,)), agu2=Agu(t, (1,))),
                      Descriptor(bounds=(n,), opcode=Opcode.RELU,
                                 agu0=Agu(t, (1,)), agu2=Agu(t, (1,)))]
        mem = jnp.asarray(rng.standard_normal(8 * n).astype(np.float32))
        sched = ClusterScheduler(descs, n_clusters=4)
        mode = sched.plan_mode()
        got = np.asarray(sched.execute(mem))
        want = np.asarray(CommandStream(descs).execute(mem))
        print(json.dumps({
            "mode": mode, "n_devices": len(jax.devices()),
            "n_used": sched.stats.get("n_devices_used"),
            "equal": bool((got == want).all())}))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["n_devices"] == 8
    assert r["mode"] == "shard_map"
    assert r["n_used"] == 4            # one device per sub-stream
    assert r["equal"]


# ----------------------------------------------------------------------
# Runtime wiring
# ----------------------------------------------------------------------
def test_serve_greedy_argmax_multistream():
    from repro.runtime.serve import greedy_argmax_multistream
    logits = RNG.standard_normal((6, 500)).astype(np.float32)
    got = greedy_argmax_multistream(logits)
    np.testing.assert_array_equal(got, logits.argmax(-1))
    # ties resolve to the first maximum, like np.argmax
    tied = np.zeros((2, 7), np.float32)
    tied[0, 3] = tied[0, 5] = 2.0
    np.testing.assert_array_equal(greedy_argmax_multistream(tied),
                                  tied.argmax(-1))


def _no_image_copy(self):
    raise AssertionError("the sampler copied its whole image to the host")


@pytest.mark.parametrize("sampler", ["greedy", "pipelined", "temperature"])
def test_serve_samplers_read_only_the_slots(sampler, monkeypatch):
    """Each sampler reads its slots through ``ProgramResult.take``, never
    through the whole-image ``numpy()``, and samples what it did."""
    import jax
    from repro.core import ProgramResult
    from repro.runtime import serve
    monkeypatch.setattr(ProgramResult, "numpy", _no_image_copy)
    logits = RNG.standard_normal((5, 300)).astype(np.float32)
    if sampler == "temperature":
        t = 0.7
        g = RNG.gumbel(size=logits.shape).astype(np.float32)
        got = serve.temperature_sample_multistream(logits, t, g)
        want = np.argmax(np.log(np.asarray(jax.nn.softmax(
            jnp.asarray(logits) / t, axis=-1))) + g, axis=-1)
    else:
        fn = (serve.greedy_argmax_program if sampler == "greedy"
              else serve.greedy_argmax_pipelined)
        got, want = fn(logits), logits.argmax(-1)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)


def test_train_update_plan_multistream():
    from repro.runtime.train import plan_update_multistream
    params = {"layer0": {"w": np.zeros((64, 64)), "b": np.zeros((64,))},
              "layer1": {"w": np.zeros((64, 64))}}
    plan = plan_update_multistream(params, n_clusters=2)
    assert plan["n_substreams"] == 3       # one stream per tensor
    assert plan["n_clusters"] == 2
    assert set(plan["assignment"]) == {0, 1}
    assert plan["model_speedup"] > 1.5


# ----------------------------------------------------------------------
# Benchmark JSON schema
# ----------------------------------------------------------------------
def test_bench_json_schema():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--json", "table1"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    doc = json.loads(out.stdout)
    assert doc["schema_version"] == 1
    rows = doc["sections"]["table1"]
    assert rows and all(set(r) == {"name", "us_per_call", "derived"}
                        for r in rows)
    assert all(isinstance(r["us_per_call"], float) for r in rows)
