"""Device idle time named by the program's own spans.

The harness's reduction (``trace_reduce``) names each idle gap by the
harness's spans (``bench.*``) alone. The program writes spans of its own
into the same profiler trace, on the same clock (``repro.core.spans``:
``serve.*`` in ``Server.generate``, ``ntx.*`` in ``Executor.run``,
``py.gc`` for a garbage collection). This module reads them and splits
the device's idle time over them:

    python3 benchmarks/chip/span_idle.py --workload serve.decode \\
        --seed <n> --seconds <s>

runs the cell with its window traced, as ``run.py --trace 1`` does, and
prints one JSON line: the cell's per-layer metrics as ``run.py`` reads
them, ``serve_tok_s`` of the traced window, the top of ``idle_by_span``,
the longest gaps named by the innermost span of either kind, and
``readings`` (:func:`readings`). ``run.py`` does not use this module: the
harness's reduction and metrics are as they were. Without a TPU it exits
2 and prints no result.

Program spans are ``(name, start_ns, end_ns, stats)``; a span's path is
its name after those of the program spans that contain it
(``serve.generate/serve.step/serve.sample``), which tells the sampler of
a decode step from the prefill's.
"""
from __future__ import annotations

import argparse
import importlib
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import trace_reduce as tr

HERE = Path(__file__).resolve().parent

Span = Tuple[str, float, float, Dict[str, object]]

#: the program's spans: ``Server.generate``, ``Executor.run``, the
#: interpreter's garbage collections
PROGRAM_PREFIXES = ("serve.", "ntx.", "py.")


def load_program_spans(path: str) -> List[Span]:
    """The program's host spans in an ``.xplane.pb``, with their stats."""
    from jax.profiler import ProfileData
    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                             for e in line.events
                             if e.name.startswith(PROGRAM_PREFIXES))
    return spans


def span_paths(spans: Sequence[Span]) -> List[str]:
    """Each span's name after those of the spans that contain it,
    outermost first, joined by ``/``."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    paths = [""] * len(spans)
    stack: List[int] = []
    for i in order:
        name, s, e = spans[i][:3]
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        parent = next((j for j in reversed(stack) if spans[j][2] >= e), None)
        paths[i] = name if parent is None else f"{paths[parent]}/{name}"
        stack.append(i)
    return paths


def _overlapping(spans: Sequence, intervals: Sequence[Tuple[float, float]]
                 ) -> Iterator[List[int]]:
    """For each of the disjoint, time-ordered ``intervals``, the indices
    of the spans that overlap it, in order (one sweep: a span leaves the
    open set once an interval starts at or after its end)."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    open_: List[int] = []
    nxt = 0
    for lo, hi in intervals:
        while nxt < len(order) and spans[order[nxt]][1] < hi:
            open_.append(order[nxt])
            nxt += 1
        open_ = [i for i in open_ if spans[i][2] > lo]
        yield sorted(open_)


def _split_gap(lo: float, hi: float,
               program: Sequence[Tuple[str, float, float]],
               harness: Sequence[tr.Event]) -> Iterator[Tuple[str, float]]:
    """``[lo, hi)`` cut where a program span starts or ends; each part is
    named by the shortest program span that covers it (its path), else by
    the harness span it falls in: ``(name, ns)``."""
    cuts = sorted({lo, hi} | {t for _, s, e in program for t in (s, e)
                              if lo < t < hi})
    for a, b in zip(cuts, cuts[1:]):
        inner = min(((e - s, path) for path, s, e in program
                     if s <= a and e >= b), key=lambda c: c[0], default=None)
        yield (inner[1] if inner else tr._span_at(harness, a, b)), b - a


def attribute(trace: tr.Trace, program: Sequence[Span],
              top: int = 10) -> Dict:
    """The device's idle time in the measured window, named by spans.

    * ``program_spans``: the program's spans clipped to the window.
    * ``gaps``: every idle interval of each device as ``(span, seconds)``,
      longest first, named as ``trace_reduce.reduce`` names them but with
      the program's spans among the candidates (the span that covers most
      of the gap, the shortest of those): with no program span, the same
      list as ``reduce``'s.
    * ``idle_by_span``: idle seconds per innermost program span, keyed by
      its path, averaged over the devices: each gap is split over the
      innermost program spans covering its parts; a part no program span
      covers goes to the harness span it falls in. The values sum to
      ``window_s - busy_s``.
    * ``breakdown``: the ``top`` longest gaps and the ``top`` of
      ``idle_by_span``, as ``[[name, seconds], ...]``.
    """
    lo, hi = tr._window(trace)
    program = [sp for sp in program if sp[2] > lo and sp[1] < hi]
    named = [sp[:3] for sp in program]
    paths = [(path, s, e)
             for path, (_, s, e) in zip(span_paths(program), named)]
    gaps: List[Tuple[str, float]] = []
    idle_ns: Dict[str, float] = {}
    n_devices = 0
    for lines in trace.devices.values():
        evs = tr._clip(lines["ops"] or lines["modules"], lo, hi)
        if not evs:
            continue
        n_devices += 1
        idle, cursor = [], lo
        for s, e in tr.union([(s, e) for _, s, e in evs]) + [(hi, hi)]:
            if s > cursor:
                idle.append((cursor, s))
            cursor = max(cursor, e)
        for (a, b), in_h, in_p in zip(idle, _overlapping(trace.spans, idle),
                                      _overlapping(program, idle)):
            harness = [trace.spans[i] for i in in_h]
            gaps.append((tr._span_at(harness + [named[i] for i in in_p],
                                     a, b), (b - a) * 1e-9))
            for name, ns in _split_gap(a, b, [paths[i] for i in in_p],
                                       harness):
                idle_ns[name] = idle_ns.get(name, 0.0) + ns
    gaps.sort(key=lambda g: -g[1])
    idle_by_span = {n: ns * 1e-9 / n_devices for n, ns in idle_ns.items()}
    top_idle = sorted(idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) * 1e-9, "n_devices": n_devices,
            "program_spans": [(n, max(s, lo), min(e, hi), st)
                              for n, s, e, st in program],
            "gaps": gaps, "idle_by_span": idle_by_span,
            "breakdown": {"idle_gaps": [[n, s] for n, s in gaps[:top]],
                          "idle_by_span": [[n, s] for n, s in top_idle]}}


def span_seconds(attributed: Dict, name: str) -> List[float]:
    """Durations of the program's spans named ``name`` in the window."""
    return [(e - s) * 1e-9 for n, s, e, _ in attributed["program_spans"]
            if n == name]


def idle_under(attributed: Dict, name: str) -> float:
    """Idle seconds that ``idle_by_span`` puts under the program's spans
    named ``name`` or inside them."""
    return sum(s for path, s in attributed["idle_by_span"].items()
               if name in path.split("/"))


def _per(total_s: float, n: int) -> Optional[float]:
    return 1e3 * total_s / n if n else None


def readings(attributed: Dict) -> Dict[str, Optional[float]]:
    """What the spans say about serving, ``None`` where there is nothing
    to read:

    * ``prefill_ms``, ``serve_step_ms``: the median ``serve.prefill``
      (a call's start to its first tokens on the host) and ``serve.step``
      (one decode iteration as the host sees it) durations;
    * ``step_idle_ms``, ``prefill_idle_ms``: device idle time under each
      ``serve.step`` / ``serve.prefill`` and the spans inside it, per span;
    * ``steps_per_call``: ``serve.step`` spans per ``serve.generate``;
    * ``program_idle_share``: the share of the idle time that program
      spans name (the rest falls under the harness's);
    * ``gc_count``, ``gc_max_ms``, ``gc_idle_ms``: the ``py.gc`` spans,
      the longest, and the idle time under them.
    """
    steps = span_seconds(attributed, "serve.step")
    prefills = span_seconds(attributed, "serve.prefill")
    calls = len(span_seconds(attributed, "serve.generate"))
    gcs = span_seconds(attributed, "py.gc")
    idle = attributed["idle_by_span"]
    total = sum(idle.values())
    named = sum(s for path, s in idle.items()
                if path.startswith(PROGRAM_PREFIXES))
    devices = attributed["n_devices"] > 0
    return {
        "prefill_ms": 1e3 * statistics.median(prefills) if prefills else None,
        "serve_step_ms": 1e3 * statistics.median(steps) if steps else None,
        "step_idle_ms": (_per(idle_under(attributed, "serve.step"),
                              len(steps)) if devices else None),
        "prefill_idle_ms": (_per(idle_under(attributed, "serve.prefill"),
                                 len(prefills)) if devices else None),
        "steps_per_call": len(steps) / calls if calls else None,
        "program_idle_share": named / total if total else None,
        "gc_count": len(gcs),
        "gc_max_ms": 1e3 * max(gcs) if gcs else None,
        "gc_idle_ms": (1e3 * sum(s for path, s in idle.items()
                                 if path.split("/")[-1] == "py.gc")
                       if devices else None),
    }


def trace_cell(bench: Dict, cell: Dict, config: Dict, traffic: Dict,
               seed: int, seconds: float) -> Dict:
    """Set up the cell and trace its window as ``run.py --trace 1`` does;
    the cell's per-layer metrics, ``serve_tok_s`` of the traced window,
    and the attribution of its idle time."""
    import jax
    import peaks
    import run
    system = importlib.import_module(f"systems.{config['system']}")
    key, rng = run.derive_key(seed)
    state = system.setup(config, traffic, key, run._span)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            window = system.window(state, seconds, run._span, rng)
        finally:
            jax.profiler.stop_trace()
        started = time.perf_counter()
        path = tr.find_xplane(log_dir)
        trace = tr.load(path)
        reduced = tr.reduce(trace)
        attributed = attribute(trace, load_program_spans(path))
        reduce_s = time.perf_counter() - started
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    ctx = {"trace": reduced, "run": window, "work": state.work,
           "peak": peaks.lookup(jax.devices()[0].device_kind)}
    metrics = {m["name"]: run.load_metric(m["name"]).read(ctx)
               for m in run.cell_metrics(bench, cell["name"], True)}
    metrics.update(window["metrics"])
    return {"metrics": metrics, "calls": window["calls"],
            "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
            "reduce_s": reduce_s, "readings": readings(attributed),
            "breakdown": attributed["breakdown"]}


def main(argv=None) -> int:
    import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    config = run.load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = run.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    print(json.dumps(trace_cell(bench, cell, config, traffic, args.seed,
                                args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
