"""Readings that the limits of ``correct`` are set from: the program's
number and the control's, per seed, for one cell.

    python3 benchmarks/chip/controls.py --workload <cell> --seeds 1 2 3 ...

One process; it needs the chip (and exits 2 without one), and it is not
part of a benchmark run. For each seed it sets the cell up, makes as
many calls as a run's check samples from (one for the descriptor
programs; enough ``generate`` calls for the sampled requests) and reads:

* ``program``: the numbers that the cell's check compares, from the
  program's own run;
* ``control``: the same numbers for the plain reference put in the
  program's place and computed one precision lower than the configuration
  states. For the fp32 descriptor programs that is a GEMM in three bf16
  passes (``Precision.HIGH``: operands split into bf16 high and low parts,
  the low-by-low product dropped) and elementwise ops in bf16; for the
  bf16 model, float8 matmuls (``reference/lm_f32.py``).

Each seed prints one JSON line; a limit lies between the largest program
reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from typing import Dict

import numpy as np

import run as harness


def _bf16(x):
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _gemm_bf16x3(a, b):
    """fp32 GEMM the way ``Precision.HIGH`` computes it: three bf16
    products with an fp32 accumulator."""
    import jax.numpy as jnp
    dot = lambda x, y: jnp.matmul(x.astype(jnp.bfloat16),
                                  y.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32)
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = a - a_hi, b - b_hi
    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def ntx_control(ops, inputs: Dict) -> Dict[str, np.ndarray]:
    """The op list one precision below fp32, on the default device."""
    import jax.numpy as jnp
    env = {n: jnp.asarray(v, jnp.float32) for n, v in inputs.items()}
    for kind, out, *args in ops:
        if kind == "gemm":
            env[out] = _gemm_bf16x3(env[args[0]], env[args[1]])
        elif kind == "axpy":
            env[out] = _bf16(_bf16(args[0] * _bf16(env[args[1]]))
                             + _bf16(env[args[2]]))
        elif kind == "relu":
            env[out] = jnp.maximum(env[args[0]], 0.0)
        elif kind == "argmax":
            env[out] = jnp.argmax(env[args[0]]).astype(jnp.float32)[None]
        else:
            raise ValueError(f"no control for op {kind!r}")
    return {n: np.asarray(v) for n, v in env.items()}


def ntx_readings(config, traffic, seed: int) -> Dict:
    from reference.ntx_f64 import compare, evaluate
    system = importlib.import_module("systems.ntx_program")
    key, rng = harness.derive_key(seed)
    st = system.setup(config, traffic, key, harness._span)
    run = system.window(st, 0.0, harness._span, rng)
    host = {n: np.asarray(a) for n, a in st.sets[0].items()}
    _, program = system.check(st, run, rng)
    ref = evaluate(st.ops, host)
    low = ntx_control(st.ops, host)
    control = {}
    for out, spec in config["checks"].items():
        control[out] = max(compare(spec["compare"], low[n], ref[n])
                           for n in system.instances(ref, st.inputs, out))
    return {"program": {k: v for k, (v, _) in program.items()},
            "control": control}


def serve_readings(config, traffic, seed: int) -> Dict:
    from reference.lm_f32 import served_gaps
    system = importlib.import_module("systems.lm_serve")
    key, rng = harness.derive_key(seed)
    st = system.setup(config, traffic, key, harness._span)
    calls = -(-traffic["check_requests"] // traffic["batch"])
    run = {"served": [c for _ in range(calls) for c in system.window(
        st, 0.0, harness._span, rng)["served"]]}
    failed, requests = system.sample(st, run, rng)
    gaps = served_gaps(config, key, requests, control=True)
    return {"malformed": failed,
            "program": {"served_logit_gap":
                        system.widest_gap(gaps["served"])},
            "control": {"served_logit_gap":
                        system.widest_gap(gaps["control"])},
            "tokens": int(sum(len(g) for g in gaps["served"]))}


READINGS = {"ntx_program": ntx_readings, "lm_serve": serve_readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = harness.load_json(harness.HERE / "configs"
                               / f"{cell['config']}.json")
    traffic = harness.load_json(harness.HERE / "traffic"
                                / f"{cell['traffic']}.json")
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("controls are read on the chip", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = READINGS[config["system"]](config, traffic, seed)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "seconds": time.perf_counter() - t0, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
