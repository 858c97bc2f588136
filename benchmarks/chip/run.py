"""Chip benchmark: one cell of ``BENCHMARK.json``, one run, one process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<mix>.json``); the configuration names the system that
serves it (``systems/<system>.py``). The run makes weights and inputs
from ``--seed``, warms up every shape the traffic uses (set-up), measures
for ``--seconds``, and checks what the measured calls produced against a
plain reference (``reference/``). With ``--trace 1`` the window is traced
and each per-layer metric that applies to the cell is read from the trace
by ``metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``busy_s`` and
``window_s`` when traced), ``breakdown`` when traced, and last ``checks``,
each compared number beside its limit (also the last lines of standard
error). Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_metric(name: str):
    """``metrics/<name>.py`` (names hold dots, so loaded by path)."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: Dict, cell: str, trace: bool):
    """The metrics this cell reports: with ``trace`` the per-layer ones
    whose cell list holds it, else its end-to-end ones."""
    if trace:
        return [m for m in bench["per_layer"] if cell in m["workloads"]]
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def derive_key(seed: int):
    """A JAX key and a numpy generator from any whole number: the seed may
    exceed what 32 bits hold, so it is hashed down first."""
    import jax
    import numpy as np
    words = np.random.SeedSequence(seed).generate_state(2)
    return (jax.random.key(int(words[0] & 0x7FFFFFFF)),
            np.random.default_rng(int(words[1])))


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache as the program's launchers keep
    it (``repro.launch.common``): JAX reads ``$JAX_COMPILATION_CACHE_DIR``
    itself where it is set, else the cache is at the fixed path
    ``<checkout>/.jax_cache``. JAX's own thresholds stand, so a program
    that compiles in under a second is compiled again by every process,
    as it is for the program's users."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def _span(name: str):
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def run_cell(bench: Dict, cell: Dict, config: Dict, traffic: Dict,
             seed: int, seconds: float, trace: bool,
             started: Optional[float] = None) -> Dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax
    import peaks
    started = time.perf_counter() if started is None else started
    system = importlib.import_module(f"systems.{config['system']}")
    devices = jax.devices()
    key, rng = derive_key(seed)
    state = system.setup(config, traffic, key, _span)
    setup_s = time.perf_counter() - started

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        run = system.window(state, seconds, _span, rng)
    finally:
        if trace:
            jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    result = {"correct": False, "attempted": run["attempted"], "failed": 0,
              "metrics": {}, "device": {
                  "platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(memory_peak)}}
    wanted = cell_metrics(bench, cell["name"], trace)
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        ctx = {"trace": reduced, "run": run, "work": state.work,
               "peak": peaks.lookup(devices[0].device_kind)}
        for m in wanted:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = reduced["breakdown"]
    else:
        for m in wanted:
            value = (setup_s if m["name"] == "setup_s"
                     else run["metrics"][m["name"]])
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    failed, checks = system.check(state, run, rng)
    result["failed"] = int(failed)
    result["correct"] = failed == 0 and all(
        v <= lim for v, lim in checks.values())
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    started = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    config = load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    sys.path.insert(0, str(ROOT / "src"))
    import repro  # noqa: F401  (the system under test must be there)
    import jax
    import peaks
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    peaks.lookup(devices[0].device_kind)
    enable_compile_cache()

    result = run_cell(bench, cell, config, traffic, args.seed, args.seconds,
                      bool(args.trace), started)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
