"""The trace reduction on a synthetic trace: busy union, idle gaps named
by the harness's host spans, device time per op and per module."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns


def _trace():
    # window 0..100 ms; on the device: module A runs 10-30 (ops 10-20 and
    # 15-30 overlap), module B runs 50-60; host: a call span 5-45 and a
    # sampling span 45-100
    ops = [("%fusion.1 = f32[8]", 10 * MS, 20 * MS),
           ("%tpu_custom_call.1 = f32[8,8]", 15 * MS, 30 * MS),
           ("%fusion.1 = f32[8]", 50 * MS, 60 * MS)]
    modules = [("jit_a(123)", 10 * MS, 30 * MS), ("jit_b(7)", 50 * MS, 60 * MS)]
    spans = [(tr.WINDOW_SPAN, 0, 100 * MS), ("bench.call", 5 * MS, 45 * MS),
             ("bench.sample", 45 * MS, 100 * MS)]
    return tr.Trace({"/device:TPU:0": {"ops": ops, "modules": modules}}, spans)


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_and_window():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.030)        # 10-30 and 50-60
    assert r["n_devices"] == 1


def test_gaps_are_named_by_the_host_span_they_fall_in():
    r = tr.reduce(_trace())
    gaps = sorted((name, round(s * 1e3, 6)) for name, s in r["gaps"])
    # 0-10: call covers 5 of 10 ms -> call; 30-50: call 15 ms vs sample
    # 5 ms -> call; 60-100 -> sample
    assert gaps == [("bench.call", 10.0), ("bench.call", 20.0),
                    ("bench.sample", 40.0)]
    assert r["breakdown"]["idle_gaps"][0] == ["bench.sample",
                                              pytest.approx(0.040)]


def test_device_time_per_op_and_module():
    r = tr.reduce(_trace())
    assert r["ops"]["%fusion.1 = f32[8]"] == pytest.approx(0.020)
    assert tr.op_seconds(r, r"^%tpu_custom_call") == pytest.approx(0.015)
    assert tr.op_seconds(r, "no such kernel") is None
    assert r["modules"]["jit_a"] == {"s": pytest.approx(0.020), "n": 1}
    assert tr.module_stats(r, r"^jit_b$") == {"s": pytest.approx(0.010),
                                              "n": 1}
    assert r["breakdown"]["device_ops"][0] == ["%fusion.1 = f32[8]",
                                               pytest.approx(0.020)]


def test_events_outside_the_window_are_clipped():
    t = _trace()
    t.spans[0] = (tr.WINDOW_SPAN, 20 * MS, 55 * MS)
    r = tr.reduce(t)
    assert r["window_s"] == pytest.approx(0.035)
    assert r["busy_s"] == pytest.approx(0.015)        # 20-30 and 50-55


def test_importing_the_reduction_loads_no_accelerator_library():
    import subprocess
    code = ("import sys; sys.path.insert(0, %r); import trace_reduce; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'libtpu')))" % str(Path(tr.__file__).parent))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True).stdout.strip()
    assert out == "[]"


def test_nested_ops_count_once_in_their_own_time():
    loop = ("%while.1 = (s32[])", 0, 10 * MS)
    body = [("%fusion.2 = bf16[8]", 1 * MS, 4 * MS),
            ("%fusion.3 = bf16[8]", 5 * MS, 9 * MS)]
    own = dict(tr.self_times([loop] + body))
    assert own == {"%while.1 = (s32[])": 3 * MS, "%fusion.2 = bf16[8]": 3 * MS,
                   "%fusion.3 = bf16[8]": 4 * MS}
    t = tr.Trace({"/device:TPU:0": {"ops": [loop] + body, "modules": []}},
                 [(tr.WINDOW_SPAN, 0, 20 * MS)])
    r = tr.reduce(t)
    assert r["busy_s"] == pytest.approx(0.010)
    assert sum(r["ops"].values()) == pytest.approx(0.010)
