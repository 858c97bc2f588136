"""Production train loop: pjit train_step + fault tolerance.

Fault-tolerance inventory (DESIGN.md §5):
  * checkpoint/restart: CheckpointManager (atomic, async, keep-k) saving
    {params, opt_state, data_state}; ``resume="auto"`` restarts from the
    newest checkpoint after any crash/preemption;
  * preemption: SIGTERM handler requests a graceful save at the next step
    boundary;
  * straggler mitigation: per-step wall-time EMA watchdog; steps slower
    than ``straggler_z`` sigma are logged with the step payload so the
    launcher can eject/replace the slow host (on CPU we log + count);
  * elastic scaling: checkpoints are mesh-agnostic; run again on a
    different mesh and the loop reshard-loads (checkpoint/elastic.py);
  * NaN fuse: non-finite loss skips the update (keeps params), counts, and
    aborts after ``max_bad_steps`` consecutive occurrences.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLM
from repro.distributed import sharding as shd
from repro.models import ArchConfig, Model
from repro.optim import AdamWConfig, apply_updates, init_opt_state


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    resume: str = "auto"            # auto | none
    straggler_z: float = 3.0
    max_bad_steps: int = 10
    seed: int = 0
    global_batch: int = 8
    seq_len: int = 128
    grad_compression: str = "none"  # none | int8 (shard_map DP reduce)
    multistream_plan: bool = True   # schedule the per-tensor update streams


def microbatches(batch, accum: int):
    """Split a batch pytree into (accum, b/accum, ...) microbatches.

    pos3 carries batch at axis 1; everything else at axis 0."""

    def split(path, leaf):
        name = getattr(path[-1], "key", None)
        if name == "pos3":
            x = leaf.reshape(leaf.shape[0], accum, -1, *leaf.shape[2:])
            return jnp.moveaxis(x, 1, 0)
        return leaf.reshape(accum, -1, *leaf.shape[1:])

    return jax.tree_util.tree_map_with_path(split, batch)


def build_step_fn(cfg: ArchConfig, opt_cfg: AdamWConfig,
                  gacc_shardings=None):
    """The raw (unjitted) train step: grads (optionally microbatch-
    accumulated into a ZeRO-sharded fp32 buffer) -> AdamW update."""
    model = Model(cfg)
    accum = max(1, cfg.grad_accum)

    def step_fn(params, opt_state, batch):
        if accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            micro = microbatches(batch, accum)
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            if gacc_shardings is not None:
                zeros = jax.lax.with_sharding_constraint(zeros,
                                                         gacc_shardings)

            def mstep(carry, mb):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(model.loss, has_aux=True)(
                    params, mb)
                gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                    gacc, g)
                if gacc_shardings is not None:
                    gacc = jax.lax.with_sharding_constraint(gacc,
                                                            gacc_shardings)
                return (gacc, lacc + l), None

            (gacc, lsum), _ = jax.lax.scan(mstep, (zeros, jnp.float32(0.0)),
                                           micro)
            grads = jax.tree.map(lambda g: g / accum, gacc)
            loss, metrics = lsum / accum, {}
        new_params, new_state = apply_updates(opt_cfg, params, grads,
                                              opt_state)
        return new_params, new_state, loss, metrics

    return step_fn


def state_shardings(cfg: ArchConfig, mesh: Mesh):
    """(param shardings, optimizer-state shardings) of the production
    DP/TP/EP layout on ``mesh`` (ZeRO-1 optimizer state)."""
    params_shape = jax.eval_shape(lambda: Model(cfg).init(0))
    pspecs = shd.param_shardings(mesh, params_shape)
    ospecs = {"master": shd.opt_state_specs(mesh, params_shape),
              "m": shd.opt_state_specs(mesh, params_shape),
              "v": shd.opt_state_specs(mesh, params_shape),
              "step": P()}
    ospecs = jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, ospecs,
        is_leaf=lambda s: isinstance(s, P))
    return pspecs, ospecs


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig,
                    mesh: Optional[Mesh] = None):
    """Build the jitted train step. With a mesh, in/out shardings are the
    production DP/TP/EP layout; without, single-device jit. The step takes
    the params and optimizer state as donations, so the new state reuses
    their buffers: the caller must not read the old ones again."""
    step_fn = build_step_fn(cfg, opt_cfg)
    donate_argnums = (0, 1)

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=donate_argnums)

    pspecs, ospecs = state_shardings(cfg, mesh)
    return jax.jit(step_fn,
                   in_shardings=(pspecs, ospecs, None),
                   out_shardings=(pspecs, ospecs,
                                  NamedSharding(mesh, P()), None),
                   donate_argnums=donate_argnums)


def plan_update_multistream(params, n_clusters: Optional[int] = None,
                            pipeline: bool = True) -> Dict[str, Any]:
    """Schedule the optimizer update as a multi-cluster descriptor program.

    Each parameter tensor's update is a dependent two-command chain over
    its own address range: the grad stream is preconditioned elementwise
    into a scratch window (MUL with the per-element preconditioner — the
    1/sqrt(v) term of an adaptive optimizer), then folded into the params
    (AXPY) — a RAW dependency through the scratch buffer. Tensors stay
    independent of each other, so the cluster scheduler load-balances the
    per-tensor chains over the mesh (layer-per-cluster, the paper's
    DNN-training split) and prices the critical path vs. serial execution.

    With ``pipeline=True`` the plan additionally level-izes the dependent
    chains into a stage pipeline (precondition stage -> apply stage) with
    explicit producer->consumer handoffs (``StageSchedule``) and reports
    the projected pipelined speedup under ``"pipeline"``.

    The program is built through :class:`repro.core.Program` — symbolic
    grad/preconditioner/scratch/param buffers per tensor, no hand-computed
    base addresses.
    """
    from repro.core import Program
    from repro.core.multistream import ClusterScheduler, StageSchedule
    leaves = jax.tree_util.tree_leaves(params)
    prog = Program()
    for ti, leaf in enumerate(leaves):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        g = prog.buffer((n,), name=f"grad{ti}")
        pre = prog.buffer((n,), name=f"precond{ti}")
        w = prog.buffer((n,), name=f"param{ti}")
        scratch = prog.mul(g, pre)            # scratch = grad * precond
        prog.axpy(-1.0, scratch, w, out=w)    # param += -lr * scratch
    descs = prog.descriptors
    if n_clusters is None:
        n_clusters = max(1, len(jax.devices()))
    sched = ClusterScheduler(descs, n_clusters=n_clusters)
    plan = {"n_substreams": len(sched.substreams),
            "n_clusters": sched.n_clusters,
            "assignment": list(sched.assignment),
            "critical_path_s": max(sched.cluster_times(), default=0.0),
            "serial_time_s": sum(sched.costs),
            "model_speedup": sched.model_speedup()}
    if pipeline:
        ss = StageSchedule(sched.graph, n_clusters=n_clusters)
        plan["pipeline"] = {
            "n_nodes": len(ss.nodes),
            "n_stages": len(ss.stages),
            "handoff_bytes": ss.stats["handoff_bytes"],
            "handoff_bytes_cross": ss.stats["handoff_bytes_cross"],
            "pipeline_time_s": ss.model_time(),
            "model_speedup": ss.model_speedup()}
    return plan


class Trainer:
    def __init__(self, cfg: ArchConfig, opt_cfg: AdamWConfig,
                 tcfg: TrainConfig, mesh: Optional[Mesh] = None):
        self.cfg, self.opt_cfg, self.tcfg = cfg, opt_cfg, tcfg
        self.mesh = mesh
        self.model = Model(cfg)
        self.step_fn = make_train_step(cfg, opt_cfg, mesh)
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep)
        self.data = SyntheticLM(cfg, tcfg.global_batch, tcfg.seq_len,
                                seed=tcfg.seed)
        self._stop_requested = False
        self.stats: Dict[str, Any] = {"straggler_events": 0, "bad_steps": 0,
                                      "resumed_from": None}

    def _sigterm(self, *_):
        self._stop_requested = True

    def run(self, steps: Optional[int] = None) -> Dict[str, Any]:
        tcfg = self.tcfg
        steps = steps or tcfg.steps
        params = self.model.init(tcfg.seed)
        opt_state = init_opt_state(params)
        start = 0
        if tcfg.multistream_plan:
            self.stats["multistream"] = plan_update_multistream(params)

        state_like = {"params": params, "opt": opt_state,
                      "data_step": jnp.zeros((), jnp.int32)}
        if tcfg.resume == "auto" and self.ckpt.latest() is not None:
            restored, ck_step = self.ckpt.restore(state_like)
            params, opt_state = restored["params"], restored["opt"]
            start = int(ck_step)
            self.data.state.step = int(restored["data_step"])
            self.stats["resumed_from"] = start

        old_handler = signal.signal(signal.SIGTERM, self._sigterm)
        ema, emvar = None, 0.0
        consecutive_bad = 0
        losses = []
        it = iter(self.data)
        try:
            for step in range(start, steps):
                batch = next(it)
                t0 = time.perf_counter()
                params, opt_state, loss, metrics = self.step_fn(
                    params, opt_state, batch)
                loss = float(loss)
                dt = time.perf_counter() - t0

                # straggler watchdog (per-step wall time z-score);
                # the first step includes compilation and is excluded
                if step == start:
                    pass
                elif ema is None:
                    ema = dt
                else:
                    if emvar > 0 and dt > ema + self.tcfg.straggler_z * np.sqrt(emvar):
                        self.stats["straggler_events"] += 1
                    emvar = 0.9 * emvar + 0.1 * (dt - ema) ** 2
                    ema = 0.9 * ema + 0.1 * dt

                # NaN fuse
                if not np.isfinite(loss):
                    self.stats["bad_steps"] += 1
                    consecutive_bad += 1
                    if consecutive_bad > tcfg.max_bad_steps:
                        raise FloatingPointError(
                            f"{consecutive_bad} consecutive non-finite steps")
                else:
                    consecutive_bad = 0
                    losses.append(loss)

                if tcfg.log_every and (step + 1) % tcfg.log_every == 0:
                    print(f"step {step + 1:5d} loss {loss:.4f} "
                          f"{dt * 1e3:.0f} ms", flush=True)
                if ((step + 1) % tcfg.ckpt_every == 0
                        or self._stop_requested or step + 1 == steps):
                    self.ckpt.save(step + 1, {
                        "params": params, "opt": opt_state,
                        "data_step": jnp.int32(self.data.state.step)})
                if self._stop_requested:
                    print("preemption requested: saved and stopping",
                          flush=True)
                    break
        finally:
            self.ckpt.wait()
            signal.signal(signal.SIGTERM, old_handler)
        return {"losses": losses, "params": params, "opt": opt_state,
                **self.stats}
