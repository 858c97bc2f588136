"""Descriptor programs through ``Executor.run``: one client, closed loop.

The configuration file names the program as data (``program``: its
inputs, name -> shape, and an op list ``[kind, out, *args]`` repeated
``repeat`` times, names getting a ``.r`` suffix per repetition), how each
output is compared with the float64 reference (``checks``), and pins the
execution policy and the kernel backend. The traffic file says how many
input sets the calls alternate over and how many calls the check samples.

Every call packs the input image, runs the program and is timed to
``block_until_ready`` of its result image, which stays on the device. The
calls alternate over the input sets; a sample of them, drawn from the
seed, and the last one are kept and compared with the reference once the
window has closed.
"""
from __future__ import annotations

import time
import zlib
from typing import Dict, List

import numpy as np

#: calls among which the sampled ones are drawn (every window reaches it)
SAMPLE_AMONG = 16


def expand(program: Dict):
    """(inputs name -> shape, op list) with the repetitions spelt out."""
    reps = int(program.get("repeat", 1))
    inputs: Dict[str, tuple] = {}
    ops: List[list] = []
    for r in range(reps):
        sfx = f".{r}" if reps > 1 else ""
        names = set(program["inputs"]) | {op[1] for op in program["ops"]}

        def ren(a):
            return a + sfx if isinstance(a, str) and a in names else a

        for name, shape in program["inputs"].items():
            inputs[name + sfx] = tuple(shape)
        ops.extend([kind] + [ren(a) for a in args]
                   for kind, *args in program["ops"])
    return inputs, ops


def instances(names, inputs, out: str) -> List[str]:
    """The names ``out`` takes in the expanded program (``out`` or its
    ``out.r`` repetitions)."""
    return [n for n in names if n not in inputs
            and (n == out or n.rsplit(".", 1)[0] == out)]


def _size(shape) -> int:
    return int(np.prod(shape, dtype=np.int64))


def work(program: Dict) -> Dict[str, float]:
    """Operations and bytes one call needs, from the shapes alone: each
    input read once and each output written once (4-byte elements);
    GEMM 2mnk operations, AXPY 2 per element, RELU and ARGMAX 1."""
    inputs, ops = expand(program)
    shapes = dict(inputs)
    flops = 0
    for kind, out, *args in ops:
        if kind == "gemm":
            (m, k), (_, n) = shapes[args[0]], shapes[args[1]]
            shapes[out] = (m, n)
            flops += 2 * m * n * k
        elif kind == "axpy":
            shapes[out] = shapes[args[1]]
            flops += 2 * _size(shapes[out])
        elif kind == "relu":
            shapes[out] = shapes[args[0]]
            flops += _size(shapes[out])
        elif kind == "argmax":
            shapes[out] = (1,)
            flops += _size(shapes[args[0]])
        else:
            raise ValueError(f"no count for op {kind!r}")
    outputs = {op[1] for op in ops} - set(inputs)
    elems = (sum(_size(s) for s in inputs.values())
             + sum(_size(shapes[o]) for o in outputs))
    return {"flops": float(flops), "bytes": float(4 * elems)}


def _build(inputs, ops):
    from repro.core import Program
    prog = Program()
    h = {}
    for kind, out, *args in ops:
        for a in args:      # inputs are declared where first read
            if isinstance(a, str) and a in inputs and a not in h:
                h[a] = prog.buffer(inputs[a], name=a)
        if kind == "gemm":
            (m, _), (_, n) = h[args[0]].shape, h[args[1]].shape
            h[out] = prog.gemm(h[args[0]], h[args[1]],
                               out=prog.buffer((m, n), name=out))
        elif kind == "axpy":
            h[out] = prog.axpy(float(args[0]), h[args[1]], h[args[2]],
                               out=prog.buffer(h[args[1]].shape, name=out))
        elif kind == "relu":
            h[out] = prog.relu(h[args[0]], out=h.get(out))
        elif kind == "argmax":
            h[out] = prog.argmax(h[args[0]], name=out)
        else:
            raise ValueError(f"no program op {kind!r}")
    return prog, h


class State:
    pass


def setup(config: Dict, traffic: Dict, key, span) -> State:
    """Inputs on the device from ``key``, the program and its executor,
    and one warm call per input set (compiles every shape the window
    uses)."""
    import jax
    import jax.numpy as jnp
    from repro.core import ExecutionPolicy, Executor
    st = State()
    st.traffic, st.checks = traffic, config["checks"]
    st.inputs, st.ops = expand(config["program"])
    st.prog, st.h = _build(st.inputs, st.ops)
    st.executor = Executor(ExecutionPolicy(policy=config["policy"],
                                           backend=config["backend"]))
    n_sets = int(traffic["input_sets"])
    names = sorted(st.inputs)

    @jax.jit
    def make(k):
        sets = []
        for s in range(n_sets):
            ks = jax.random.fold_in(k, s)
            sets.append({n: jax.random.uniform(
                jax.random.fold_in(ks, zlib.crc32(n.encode())),
                st.inputs[n], jnp.float32, -1.0, 1.0) for n in names})
        return sets

    st.sets = make(key)
    st.bindings = [{st.h[n]: arrs[n] for n in names} for arrs in st.sets]
    st.work = work(config["program"])
    with span("bench.warm"):
        for b in st.bindings:
            jax.block_until_ready(st.executor.run(st.prog, b).mem)
    return st


def window(st: State, seconds: float, span, rng) -> Dict:
    """Back-to-back ``Executor.run`` calls for ``seconds``."""
    keep = set(rng.choice(SAMPLE_AMONG, size=int(st.traffic["sample_calls"]),
                          replace=False).tolist())
    times, kept = [], {}
    n_sets = len(st.bindings)
    i = 0
    with span("bench.window"):
        start = time.perf_counter()
        while True:
            with span("bench.call"):
                t0 = time.perf_counter()
                res = st.executor.run(st.prog, st.bindings[i % n_sets])
                res.mem.block_until_ready()
                t1 = time.perf_counter()
            times.append(t1 - t0)
            if i in keep:
                kept[i] = res
            i += 1
            if t1 - start >= seconds:
                break
    kept[i - 1] = res
    times = np.asarray(times)
    return {"attempted": int(len(times)), "calls": int(len(times)),
            "window_s": float(t1 - start), "kept": kept,
            "metrics": {"program_ms": float((t1 - start) / len(times) * 1e3),
                        "program_p95_ms": float(np.percentile(times, 95)
                                                * 1e3)}}


def check(st: State, run: Dict, rng) -> tuple:
    """Compare the kept calls' outputs with the float64 reference of
    their input set. Returns (failed answers, {name: (value, limit)})."""
    from reference.ntx_f64 import compare, evaluate
    n_sets = len(st.sets)
    host_sets = [{n: np.asarray(a) for n, a in s.items()} for s in st.sets]
    st.sets = st.bindings = None
    refs = {}
    worst: Dict[str, float] = {}
    failed = 0
    checks = st.checks
    for i, res in sorted(run["kept"].items()):
        s = i % n_sets
        if s not in refs:
            refs[s] = evaluate(st.ops, host_sets[s])
        img = res.numpy()
        bad = False
        for out, spec in checks.items():
            for name in instances(st.h, st.inputs, out):
                lo, hi = st.h[name].span
                v = compare(spec["compare"], img[lo:hi], refs[s][name])
                worst[out] = max(worst.get(out, 0.0), v)
                bad |= not v <= spec["limit"]
        failed += bad
    run["kept"] = None
    return failed, {out: (worst[out], spec["limit"])
                    for out, spec in checks.items()}
