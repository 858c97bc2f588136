"""Bring-up check: the main paths of this repo, run once on a TPU.

    python chip_smoke.py               # one chip: phases "descriptor", "serve"
    python chip_smoke.py --four-chips  # four chips: phase "four_chips" only

One process; it starts no child. Every phase prints its checks as JSON
lines, each measured value beside its tolerance; the last line of standard
output is ``{"ok": true, "device": {...}}`` and is printed only when every
phase passed. Without a TPU (e.g. ``JAX_PLATFORMS=cpu``) it exits 2 before
any phase runs.

* ``descriptor`` — the paper's workload: ``ntx.Program``s through
  ``Executor(ExecutionPolicy(backend="pallas"))`` — a 4096^3 fp32 GEMM, an
  AXPY -> RELU chain with ARGMAX tails over 2^26 elements, MAX and ARGMAX
  reductions over independent rows, a 3x3 conv and a 2-D Laplace stencil
  at 4096^2 — each against a float64 numpy reference of the same inputs.
* ``serve`` — granite-3-8b at published widths, depth cut to 16 of 40
  layers, through the serve launcher: batch 8, 512-token prompts, 32 greedy
  tokens; then decode logits against a prefill over prompt + token.
* ``four_chips`` — the descriptor program's shard_map cluster transport
  against its vmap transport on one chip, and 3 steps of the sharded train
  step on a (4, 1) data mesh against the same steps unsharded.

Inputs and weights are random, made from ``--seed``.
"""
import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# The sizes the checks run at (module constants, so a rehearsal on the CPU
# can shrink them and switch BACKEND to "pallas_interpret").
BACKEND = "pallas"
GEMM_N = 4096
CHAIN_ROWS, CHAIN_N = 4, 1 << 24           # 2^26 elements per operand
RED_ROWS, RED_N = 8, 1 << 22
PLANE_N = 4096
SERVE_FLAGS = ["--arch", "granite-3-8b", "--set", "n_layers=16",
               "--batch", "8", "--prompt-len", "512", "--new-tokens", "32"]
MESH_ROWS, MESH_N = 8, 1 << 20
TRAIN_ARCH, TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ = "granite-3-8b", 1, 8, 128


def log(**fields):
    print(json.dumps(fields, default=str), flush=True)


class Checks:
    """Collects one phase's checks; the phase fails if any check fails."""

    def __init__(self, phase: str):
        self.phase, self.failed = phase, []

    def __call__(self, name: str, ok: bool, **values):
        log(phase=self.phase, check=name, ok=bool(ok), **values)
        if not ok:
            self.failed.append(name)


def uniform(rng, shape):
    return (rng.random(shape, dtype=np.float32) * 2 - 1).astype(np.float32)


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


# ----------------------------------------------------------------------
# phase "descriptor"
# ----------------------------------------------------------------------
def run_program(chk, name, prog, inputs, policy):
    import jax
    from repro.core import ExecutionPolicy, Executor
    auto = Executor(ExecutionPolicy(backend=BACKEND)).plan(prog)["policy"]
    ex = Executor(ExecutionPolicy(policy=policy, backend=BACKEND))
    t0 = time.perf_counter()
    res = ex.run(prog, inputs)
    jax.block_until_ready(res.mem)
    sched = ex.stats["scheduler"] or {}
    log(phase=chk.phase, program=name, auto_would_pick=auto,
        policy=ex.stats["policy"], mode_used=sched.get("mode_used"),
        n_descriptors=ex.stats["n_descriptors"],
        first_run_s_incl_compile=time.perf_counter() - t0)
    return res


def phase_descriptor(chk, seed):
    from repro.core import Program
    rng = np.random.default_rng(seed)

    # GEMM 4096^3 fp32 against float64 numpy, as max |error| over C's rms.
    # Pallas side: fp32 operands at Precision.HIGHEST, fp32 accumulate --
    # the paper's fp32 FMA; fp32 rounding over k = 4096 predicts ~1e-5.
    # Controls through the same comparison: XLA's jnp.dot at HIGHEST (fp32),
    # HIGH (3 bf16 passes, ~1e-4 predicted) and DEFAULT (1 bf16 pass, ~1e-2).
    # Tolerance: half the HIGH control's error, so a kernel that drops
    # below fp32 to 3 bf16 passes fails.
    import jax
    import jax.numpy as jnp
    n = GEMM_N
    a, b = uniform(rng, (n, n)), uniform(rng, (n, n))
    p = Program()
    ha, hb = p.buffer((n, n), name="a"), p.buffer((n, n), name="b")
    hc = p.gemm(ha, hb)
    got = run_program(chk, f"gemm_{n}", p, {ha: a, hb: b}, "fused")[hc]
    want = a.astype(np.float64) @ b.astype(np.float64)
    rms = np.sqrt(np.mean(want ** 2))
    err = {"kernel": float(np.abs(got - want).max() / rms)}
    for prec in ("HIGHEST", "HIGH", "DEFAULT"):
        dot = jax.jit(lambda x, y, prec=prec: jnp.dot(
            x, y, precision=getattr(jax.lax.Precision, prec)))
        c = np.asarray(dot(jnp.asarray(a), jnp.asarray(b)), np.float64)
        err[f"xla_{prec}"] = float(np.abs(c - want).max() / rms)
    tol = err["xla_HIGH"] / 2
    chk("gemm_vs_f64", err["kernel"] <= tol, max_err_over_rms=err, tol=tol)
    del a, b, got, want, p

    # AXPY -> RELU with an ARGMAX tail, 2^26 elements per operand. The
    # ARGMAX index is written back as fp32, exact only up to 2^24, so the
    # stream is 4 rows of 2^24, each its own fused chain + tail. The chain
    # is fp32 on both Pallas and XLA sides: fl(fl(alpha*x) + y) is within
    # 2^-23 * (|alpha*x| + |y|) of the exact value; tolerance 2^-22 of
    # that. The tail must pick the first maximum of the chain's own values.
    rows, n, alpha = CHAIN_ROWS, CHAIN_N, 0.7
    p = Program()
    hx, hy, hz, hi = [], [], [], []
    for r in range(rows):
        hx.append(p.buffer(n, name=f"x{r}"))
        hy.append(p.buffer(n, name=f"y{r}"))
        hz.append(p.axpy(alpha, hx[r], hy[r]))
        p.relu(hz[r], out=hz[r])
        hi.append(p.argmax(hz[r], name=f"i{r}"))
    xs = [uniform(rng, n) for _ in range(rows)]
    ys = [uniform(rng, n) for _ in range(rows)]
    inputs = dict(zip(hx, xs))
    inputs.update(zip(hy, ys))
    res = run_program(chk, f"axpy_relu_argmax_{rows}x{n}", p, inputs,
                      "fused")
    worst, idx_ok = 0.0, True
    for r in range(rows):
        x64, y64 = xs[r].astype(np.float64), ys[r].astype(np.float64)
        want = np.maximum(alpha * x64 + y64, 0.0)
        got = res[hz[r]]
        bound = np.abs(alpha * x64) + np.abs(y64) + 1e-30
        worst = max(worst, float(np.max(np.abs(got - want) / bound)))
        idx_ok &= int(res[hi[r]][0]) == int(np.argmax(got))
    chk("chain_vs_f64", worst <= 2 ** -22, max_rel_err=worst, tol=2 ** -22)
    chk("argmax_tail_first_max", idx_ok)
    del xs, ys, inputs, res, p

    # MAX and ARGMAX over 8 independent rows of 2^22: one sub-stream per
    # row, stacked by the multistream scheduler. Comparisons and the index
    # counter are exact, so both must match numpy bit for bit.
    rows, n = RED_ROWS, RED_N
    data = [uniform(rng, n) for _ in range(rows)]
    for op in ("max", "argmax"):
        p = Program()
        hr, hs = [], []
        for i in range(rows):       # row, then its slot: uniform lanes
            hr.append(p.buffer(n, name=f"r{i}"))
            hs.append(p.reduce(op, hr[-1]))
        res = run_program(chk, f"{op}_{rows}x{n}", p, dict(zip(hr, data)),
                          "multistream")
        want = [np.max(d) if op == "max" else np.argmax(d) for d in data]
        got = [res[h][0] for h in hs]
        chk(f"{op}_rows_exact", all(float(g) == float(w)
                                    for g, w in zip(got, want)))
    del data, p, res

    # 3x3 conv and the 2-D Laplace stencil at 4096^2: fp32 accumulate of
    # 9 (conv) or 3 + 3 + 1 (stencil passes + sum) terms on the Pallas
    # side, float64 reference; error bound ~ terms * 2^-24 of the sum of
    # |terms|: tolerance 2^-19 of it.
    n = PLANE_N
    img, ker = uniform(rng, (n, n)), uniform(rng, (3, 3))
    p = Program()
    himg, hker = p.buffer((n, n), name="img"), p.buffer((3, 3), name="ker")
    hout = p.conv2d(himg, hker)
    got = run_program(chk, f"conv3x3_{n}", p, {himg: img, hker: ker},
                      "fused")[hout]
    i64, k64 = img.astype(np.float64), ker.astype(np.float64)
    want = np.zeros((n - 2, n - 2))
    scale = np.zeros((n - 2, n - 2))
    for i in range(3):
        for j in range(3):
            term = k64[i, j] * i64[i:i + n - 2, j:j + n - 2]
            want += term
            scale += np.abs(term)
    err = float(np.max(np.abs(got - want) / scale))
    chk("conv3x3_vs_f64", err <= 2 ** -19, max_rel_err=err, tol=2 ** -19)
    del got, want, scale, p

    p = Program()
    hx = p.buffer((n, n), name="x")
    hcoef = p.buffer(3, name="coef", init=[1.0, -2.0, 1.0])
    hlap = p.laplace2d(hx, hcoef)
    got = run_program(chk, f"laplace2d_{n}", p, {hx: img}, "fused")[hlap]
    c = i64[1:-1, 1:-1]
    nbrs = (i64[2:, 1:-1], i64[:-2, 1:-1], i64[1:-1, 2:], i64[1:-1, :-2])
    want = sum(nbrs) - 4.0 * c
    scale = sum(np.abs(t) for t in nbrs) + 4.0 * np.abs(c)
    err = float(np.max(np.abs(got - want) / scale))
    chk("laplace2d_vs_f64", err <= 2 ** -19, max_rel_err=err, tol=2 ** -19)


# ----------------------------------------------------------------------
# phase "serve"
# ----------------------------------------------------------------------
def phase_serve(chk, seed):
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.launch import serve as launcher

    args = launcher._parse(SERVE_FLAGS + ["--seed", str(seed)])
    batch, prompt_len, new_tokens = (args.batch, args.prompt_len,
                                     args.new_tokens)
    cfg, srv = launcher.load_server(args)
    leaves = jax.tree.leaves(srv.params)
    log(phase=chk.phase, arch=cfg.name,
        widths=dict(d_model=cfg.d_model, n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                    d_ff=cfg.d_ff, vocab=cfg.vocab),
        depth_cut=f"{cfg.n_layers} of {configs.get(args.arch).n_layers} "
                  "layers (full depth does not fit 16 GB of HBM)",
        params_billions=sum(x.size for x in leaves) / 1e9,
        param_gbytes=sum(x.nbytes for x in leaves) / 1e9)

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (batch, prompt_len))
    out = srv.generate(list(prompts))
    comps = out["completions"]
    in_vocab = (len(comps) == batch
                and all(len(c) == new_tokens for c in comps)
                and all(0 <= t < cfg.vocab for c in comps for t in c))
    log(phase=chk.phase,
        time_to_first_tokens_s_host_clock=out["prefill_s"],
        decode_tok_per_s_host_clock=out["decode_tok_per_s"],
        first_completion=comps[0][:8])
    chk("completions_in_vocab", in_vocab,
        n_requests=len(comps), tokens_each=sorted({len(c) for c in comps}))

    # Cached decode vs a fresh prefill over prompt + token, as logits.
    # Both sides run bf16 weights and activations (8-bit mantissa, 2^-9
    # relative rounding) with a bf16 KV cache, and differ in matmul shapes
    # and summation order; 16 layers compound that to ~1e-2 relative rms.
    # A wrong cache position or slot gives O(1). Tolerance 5e-2.
    model, params = srv.model, srv.params
    cache_len = srv.scfg.max_seq
    toks = jnp.asarray(prompts, jnp.int32)
    logits, cache, fill = model.prefill(params, {"tokens": toks},
                                        cache_len=cache_len)
    nxt = jnp.argmax(logits[:, :cfg.vocab], -1).astype(jnp.int32)[:, None]
    dec, _ = jax.jit(model.decode)(params, nxt, cache, jnp.int32(fill))
    full, _, _ = model.prefill(
        params, {"tokens": jnp.concatenate([toks, nxt], 1)},
        cache_len=cache_len)
    d = np.asarray(dec[:, 0, :cfg.vocab], np.float32)
    f = np.asarray(full[:, :cfg.vocab], np.float32)
    err = rel_rms(d, f)
    chk("decode_vs_prefill_logits", np.isfinite(d).all() and err <= 5e-2,
        rel_rms_err=err, max_abs_err=float(np.abs(d - f).max()),
        logits_rms=float(np.sqrt(np.mean(f ** 2))), tol=5e-2,
        top1_agree=float(np.mean(d.argmax(-1) == f.argmax(-1))))


# ----------------------------------------------------------------------
# phase "four_chips"
# ----------------------------------------------------------------------
def phase_four_chips(chk, seed):
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.core import ExecutionPolicy, Executor, Program
    from repro.distributed.mesh import make_mesh
    from repro.models import Model
    from repro.models.common import set_activation_sharding
    from repro.optim import AdamWConfig, init_opt_state
    from repro.runtime.serve import _ARGMAX_PROGRAMS, greedy_argmax_program
    from repro.runtime.train import make_train_step, state_shardings

    rng = np.random.default_rng(seed)
    # Descriptor program on the cluster mesh: 8 independent AXPY -> RELU ->
    # ARGMAX rows of 2^20, shard_map over the 4 chips vs the vmap
    # transport on one chip. Same kernels per lane, so bit-equal.
    rows, n = MESH_ROWS, MESH_N
    p = Program()
    inputs, outs = {}, []
    for r in range(rows):
        x, y = p.buffer(n), p.buffer(n)
        z = p.axpy(0.7, x, y)
        p.relu(z, out=z)
        outs.append((z, p.argmax(z)))
        inputs[x], inputs[y] = uniform(rng, n), uniform(rng, n)
    images = {}
    for transport in ("shard_map", "vmap"):
        ex = Executor(ExecutionPolicy(policy="multistream", backend=BACKEND,
                                      transport=transport))
        res = ex.run(p, inputs)
        images[transport] = res.numpy()
        st = ex.stats["scheduler"]
        log(phase=chk.phase, program=f"axpy_relu_argmax_{rows}x{n}",
            transport=transport, mode_used=st["mode_used"],
            n_devices_used=st.get("n_devices_used"),
            result_devices=sorted(d.id for d in res.mem.devices()))
    chk("shard_map_bit_equal_vmap",
        np.array_equal(images["shard_map"].view(np.uint32),
                       images["vmap"].view(np.uint32)))
    del images, inputs, p

    # The serve sampler on this host also becomes a shard_map over the
    # chips (n_clusters defaults to the device count): where its operands
    # and results land, and that it still equals numpy's argmax.
    logits = jnp.asarray(uniform(rng, (8, 49155)))
    ids = greedy_argmax_program(logits)
    st = _ARGMAX_PROGRAMS[(8, 49155)][1].stats["scheduler"]
    log(phase=chk.phase, program="serve_sampler_b8", mode_used=st["mode_used"],
        n_devices_used=st.get("n_devices_used"),
        logits_devices=sorted(d.id for d in logits.devices()))
    chk("sampler_equals_argmax",
        np.array_equal(ids, np.asarray(logits).argmax(-1)))

    # Train step at granite-3-8b widths, 1 layer (~0.6 B params, ~11 GB of
    # bf16 weights, fp32 master/m/v and fp32 grads: one chip holds it
    # unsharded), 3 steps on a (4, 1) data mesh vs the same 3 steps
    # unsharded on one chip. The data-parallel gradient all-reduce sums in
    # another order, which can flip the last bit of bf16 weights after an
    # update; the loss (~ln 49155) must agree to 1e-3 relative.
    cfg = configs.get(TRAIN_ARCH).scaled(n_layers=TRAIN_LAYERS)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=3)
    shape = (TRAIN_BATCH, TRAIN_SEQ)
    batches = [{"tokens": jnp.asarray(rng.integers(0, cfg.vocab, shape),
                                      jnp.int32),
                "labels": jnp.asarray(rng.integers(0, cfg.vocab, shape),
                                      jnp.int32)} for _ in range(3)]
    losses = {}
    mesh = make_mesh((4, 1), ("data", "model"))
    set_activation_sharding(mesh, ("data",), "model")
    try:
        pspecs, ospecs = state_shardings(cfg, mesh)
        params = jax.device_put(Model(cfg).init(seed), pspecs)
        opt = jax.jit(init_opt_state, out_shardings=ospecs)(params)
        step = make_train_step(cfg, opt_cfg, mesh)
        losses["sharded_4x1"] = []
        for b in batches:
            params, opt, loss, _ = step(params, opt, b)
            losses["sharded_4x1"].append(float(loss))
        del params, opt
    finally:
        set_activation_sharding()
    params = Model(cfg).init(seed)
    opt = jax.jit(init_opt_state)(params)
    step = make_train_step(cfg, opt_cfg, None)
    losses["one_chip"] = []
    for b in batches:
        params, opt, loss, _ = step(params, opt, b)
        losses["one_chip"].append(float(loss))
    del params, opt
    a, b = np.asarray(losses["sharded_4x1"]), np.asarray(losses["one_chip"])
    err = float(np.max(np.abs(a - b) / np.abs(b)))
    chk("sharded_vs_unsharded_loss", np.isfinite(a).all() and err <= 1e-3,
        losses=losses, max_rel_diff=err, tol=1e-3)


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip path (mesh, shard_map)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, SRC)
    from repro.launch.common import enable_compile_cache

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"no TPU: JAX sees {device['count']} {device['platform']} "
              f"device(s) ({device['kind']}); this check runs only on a TPU",
              file=sys.stderr)
        return 2
    log(device=device)
    if args.four_chips and device["count"] < 4:
        print(f"--four-chips needs 4 chips, JAX sees {device['count']}",
              file=sys.stderr)
        return 2

    log(compile_cache=enable_compile_cache())

    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("descriptor", phase_descriptor), ("serve", phase_serve)])
    failed = []
    for name, fn in phases:
        chk = Checks(name)
        t0 = time.perf_counter()
        try:
            fn(chk, args.seed)
        except Exception:
            traceback.print_exc()
            chk.failed.append("raised")
        log(phase=name, passed=not chk.failed, failed_checks=chk.failed,
            seconds=time.perf_counter() - t0)
        if chk.failed:
            failed.append(name)
    if failed:
        print(f"failed phases: {failed}", file=sys.stderr)
        return 1
    log(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
