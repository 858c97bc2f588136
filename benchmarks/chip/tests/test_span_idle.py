"""Device idle time named by the program's spans (``span_idle``): split
exactly over the innermost spans on a synthetic trace, the harness's gaps
unchanged where the trace has no program span, the sweep equal to a
direct reading of every gap, and the readings on a small serve cell
traced on the CPU."""
import random
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import span_idle as si  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns
G, P, S = "serve.generate", "serve.generate/serve.prefill", \
    "serve.generate/serve.step"


def _served():
    """Window 0-100 ms; the device busy 10-30 and 50-60; one call with a
    prefill and one decode step, a garbage collection in its sampler."""
    ops = [("%fusion.1", 10 * MS, 30 * MS), ("%fusion.2", 50 * MS, 60 * MS)]
    harness = [(tr.WINDOW_SPAN, 0, 100 * MS),
               ("bench.generate", 2 * MS, 98 * MS)]
    program = [(n, s * MS, e * MS, st) for n, s, e, st in [
        ("serve.generate", 4, 96, {"call": 0}),
        ("serve.prefill", 4, 40, {}),
        ("serve.forward", 4, 12, {"phase": "prefill"}),
        ("serve.sample", 12, 40, {"phase": "prefill"}),
        ("ntx.run", 14, 38, {}), ("ntx.execute", 20, 35, {}),
        ("serve.readback", 38, 40, {}),
        ("serve.step", 40, 96, {"step": 0, "active": 16}),
        ("serve.emit", 40, 42, {}),
        ("serve.forward", 42, 45, {"phase": "decode"}),
        ("serve.sample", 45, 96, {"phase": "decode"}),
        ("py.gc", 70, 80, {"gen": 0})]]
    trace = tr.Trace({"/device:TPU:0": {"ops": ops, "modules": []}}, harness)
    return trace, program


def _ms(d):
    return {k: round(v * 1e3, 9) for k, v in d.items()}


def test_idle_is_split_exactly_over_the_innermost_spans():
    trace, program = _served()
    a = si.attribute(trace, program)
    # 0-4 and 96-100 lie outside the call: named as a gap would be
    assert _ms(a["idle_by_span"]) == {
        "bench.generate": 8.0,
        f"{P}/serve.forward": 6.0,
        f"{P}/serve.sample/ntx.run/ntx.execute": 5.0,
        f"{P}/serve.sample/ntx.run": 3.0,
        f"{P}/serve.sample/serve.readback": 2.0,
        f"{S}/serve.emit": 2.0, f"{S}/serve.forward": 3.0,
        f"{S}/serve.sample": 31.0, f"{S}/serve.sample/py.gc": 10.0}
    reduced = tr.reduce(trace)
    assert sum(a["idle_by_span"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    # a gap is named by the span that covers most of it, the shortest
    assert sorted((n, round(s * 1e3, 9)) for n, s in a["gaps"]) == [
        ("bench.generate", 10.0), ("bench.generate", 40.0), (G, 20.0)]
    assert a["breakdown"]["idle_by_span"][0] == [f"{S}/serve.sample",
                                                 pytest.approx(0.031)]


def test_readings():
    trace, program = _served()
    r = si.readings(si.attribute(trace, program))
    assert r["prefill_ms"] == pytest.approx(36.0)
    assert r["serve_step_ms"] == pytest.approx(56.0)
    assert r["step_idle_ms"] == pytest.approx(46.0)
    assert r["prefill_idle_ms"] == pytest.approx(16.0)
    assert r["steps_per_call"] == 1.0
    assert r["program_idle_share"] == pytest.approx(62 / 70)
    assert (r["gc_count"], r["gc_max_ms"]) == (1, pytest.approx(10.0))
    assert r["gc_idle_ms"] == pytest.approx(10.0)


def test_readings_without_program_spans_are_empty():
    trace, _ = _served()
    r = si.readings(si.attribute(trace, []))
    assert r["prefill_ms"] is r["serve_step_ms"] is r["step_idle_ms"] is None
    assert r["steps_per_call"] is None and r["gc_count"] == 0


def _random_trace(rng, with_program):
    """Integer-ms timeline: busy ops on two devices, harness spans, and
    program spans nested three deep."""
    devices = {}
    for d in range(2):
        ops, t = [], 0
        while t < 90:
            t += rng.randint(0, 6)
            end = t + rng.randint(1, 6)
            ops.append((f"op{t}", t * MS, end * MS))
            t = end
        devices[f"/device:TPU:{d}"] = {"ops": ops, "modules": []}
    harness = [(tr.WINDOW_SPAN, 0, 100 * MS)]
    for _ in range(4):
        s = rng.randint(0, 95)
        harness.append((f"bench.s{s}", s * MS, rng.randint(s + 1, 100) * MS))
    program, t = [], rng.randint(0, 5)
    while with_program and t < 90:
        end = min(100, t + rng.randint(5, 30))
        program.append(("serve.step", t * MS, end * MS, {}))
        inner = t + rng.randint(0, 3)
        while inner < end - 1:
            stop = min(end, inner + rng.randint(1, 8))
            program.append(("serve.sample", inner * MS, stop * MS, {}))
            if stop - inner > 2:
                program.append(("ntx.run", (inner + 1) * MS, (stop - 1) * MS,
                                {}))
            inner = stop + rng.randint(0, 3)
        t = end + rng.randint(0, 4)
    return tr.Trace(devices, harness), program


@pytest.mark.parametrize("seed", range(6))
def test_sweep_equals_a_direct_reading_of_each_gap(seed):
    trace, program = _random_trace(random.Random(seed), seed % 3 != 0)
    a = si.attribute(trace, program)
    if not program:                 # the harness's own gaps, unchanged
        assert a["gaps"] == tr.reduce(trace)["gaps"]
    paths = si.span_paths(program)
    named = [sp[:3] for sp in program]
    want_gaps, want_idle = [], {}

    def add(name, n_ms):
        want_idle[name] = want_idle.get(name, 0) + n_ms

    for lines in trace.devices.values():
        busy = tr.union([(s, e) for _, s, e in lines["ops"]])
        cursor = 0
        for start, end in busy + [(100 * MS, 100 * MS)]:
            if start > cursor:
                want_gaps.append((tr._span_at(trace.spans + named, cursor,
                                              start), (start - cursor) * 1e-9))
                bare = []           # a run of milliseconds no span covers
                for ms in range(cursor // MS, start // MS):
                    cover = [(e - s, i) for i, (_, s, e, _) in
                             enumerate(program)
                             if s <= ms * MS and (ms + 1) * MS <= e]
                    if cover:
                        add(paths[min(cover)[1]], 1)
                    else:
                        bare.append(ms)
                    if bare and (cover or ms + 1 == start // MS):
                        add(tr._span_at(trace.spans, bare[0] * MS,
                                        (bare[-1] + 1) * MS), len(bare))
                        bare = []
            cursor = max(cursor, end)
    assert sorted(a["gaps"]) == sorted(want_gaps)
    assert _ms(a["idle_by_span"]) == {n: v / 2 for n, v in want_idle.items()}


def test_span_paths_follow_nesting():
    spans = [("a", 0, 10, {}), ("b", 1, 4, {}), ("c", 2, 3, {}),
             ("b", 5, 9, {}), ("d", 12, 13, {})]
    assert si.span_paths(spans) == ["a", "a/b", "a/b/c", "a/b", "d"]


def test_a_small_serve_cell_traced_on_the_cpu(monkeypatch):
    """The chain from the program's spans to the readings, on a real
    trace: on the CPU the trace holds no device plane, so only the span
    durations and counts can be read."""
    import jax
    import peaks
    import run as harness
    from test_chip_harness import small_lm
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    config, traffic = small_lm()
    out = si.trace_cell(bench, bench["workloads"][0], config, traffic,
                        seed=2 ** 33 + 5, seconds=0.0)
    r = out["readings"]
    assert out["calls"] == 1 and r["steps_per_call"] == traffic["new_tokens"]
    assert r["prefill_ms"] > 0 and r["serve_step_ms"] > 0
    assert r["step_idle_ms"] is None        # no device events on the CPU
    assert out["metrics"]["serve_tok_s"] > 0
    assert set(out["metrics"]) >= {"decode_step_ms", "device_idle.serve",
                                   "mfu.serve"}
