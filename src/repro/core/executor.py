"""``Executor`` — the unified, policy-driven front door for NTX programs.

An :class:`Executor` holds an :class:`ExecutionPolicy` (backend, cluster
count, transport, memory hierarchy, autotune mode — the knob that
replaces the ``NTX_AUTOTUNE`` env var) and ``run``s a
:class:`~repro.core.program.Program` under one of five execution
policies:

==============  =====================================================
``serial``      per-descriptor :func:`~repro.core.dispatch.dispatch`
``fused``       one fused :class:`~repro.core.stream.CommandStream`
``multistream`` independent sub-streams over the cluster mesh
                (:class:`~repro.core.multistream.ClusterScheduler`)
``pipeline``    dependent stages with inter-cluster handoffs
                (:class:`~repro.core.multistream.StageSchedule`)
``tiled``       out-of-core double-buffered tile loops through TCDM
                (:class:`~repro.core.tiling.TilePlan`)
==============  =====================================================

``policy="auto"`` (the default) first consults the capacity model: a
program whose working set exceeds the cluster TCDM
(:func:`repro.core.memory.fits`) cannot faithfully run resident, so it
is transparently tiled (``perfmodel.ntx.tiling_gain`` records the
verdict and the double-buffer roofline). Programs that fit are scored
with the paper-derived gain ratios in ``repro.perfmodel.ntx`` —
``stream_fusion_gain`` for fused-vs-serial, ``multistream_gain``/
``pipeline_gain`` for the mesh layers (both priced on top of fused
sub-streams, so their speedups compose multiplicatively with the fusion
gain) — and the highest-scoring policy wins, preferring the simpler one
on ties. With ``ExecutionPolicy(autotune="measure")`` the auto decision
is *measured* instead of modeled: the candidate policies race once per
program (cached like the GEMM-block autotune memo), so on CPU the
stacked-vmap transport wins even when the hardware model prefers the
mesh. An explicit ``executor.run(program, policy="pipeline")`` overrides
per call. Every policy is semantically equal (bit-equal for
streaming/reduction programs); the choice is purely a performance
decision, which is why a model (or a stopwatch) can make it.

Plans (fusion groups, schedules, tile plans, jitted stacked transports)
are cached on the program object keyed by its mutation version, so
steady-state loops — a serving decode step, for instance — pay one
dispatch per call.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .cluster import NtxClusterSpec, PAPER_CLUSTER
from .descriptor import Descriptor
from .memory import NtxMemSpec
from .program import Program, ProgramResult
from .spans import span

POLICIES = ("auto", "serial", "fused", "multistream", "pipeline", "tiled")
TRANSPORTS = ("auto", "vmap", "shard_map", "interleave", "serial",
              "overlap")
#: auto-selection moves past a simpler policy only on a real win
_EPS = 1e-9

#: measured auto-policy picks, keyed like the autotune memo: the program
#: (descriptors are hashable), cluster count, transport, backend and
#: spec — everything that changes which candidate would win a race
_MEASURED_POLICY: Dict[tuple, Dict] = {}


def clear_measured_policy_cache() -> None:
    """Drop every measured auto-policy pick (``autotune="measure"``).

    Call after changing the execution environment in ways the memo key
    cannot see (e.g. moving the process to different hardware)."""
    _MEASURED_POLICY.clear()


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """How an :class:`Executor` runs programs.

    ``policy``      auto | serial | fused | multistream | pipeline | tiled.
    ``backend``     kernel backend for the run (ref | pallas_interpret |
                    pallas); ``None`` keeps the process-wide setting.
    ``n_clusters``  cluster-mesh width for the graph policies; ``None``
                    means one cluster per visible device.
    ``transport``   how scheduled sub-streams execute (auto | vmap |
                    shard_map | interleave | serial | overlap — the
                    scheduler modes; ``overlap`` runs the stage pipeline
                    with DMA-in overlapped across stage boundaries).
    ``autotune``    GEMM block autotune mode (model | measure) for the
                    run; ``None`` keeps the process setting (which itself
                    falls back to the deprecated ``NTX_AUTOTUNE`` env
                    var). ``measure`` also switches the *auto policy*
                    decision from the hardware model to a one-off race of
                    the candidate policies.
    ``mem``         the cluster memory hierarchy the capacity model and
                    the tiled policy use; ``None`` derives it from
                    ``spec`` (:meth:`NtxMemSpec.from_cluster`).
    ``dma_overlap`` whether tiled execution software-pipelines tile i+1's
                    DMA-in under tile i's compute (the double-buffered
                    machine) or stalls phase-by-phase (no DMA engine).
    """

    policy: str = "auto"
    backend: Optional[str] = None
    n_clusters: Optional[int] = None
    transport: str = "auto"
    autotune: Optional[str] = None
    spec: NtxClusterSpec = PAPER_CLUSTER
    setup_cycles: int = 100
    mem: Optional[NtxMemSpec] = None
    dma_overlap: bool = True

    def __post_init__(self):
        if self.policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, "
                             f"got {self.policy!r}")
        if self.transport not in TRANSPORTS:
            raise ValueError(f"transport must be one of {TRANSPORTS}, "
                             f"got {self.transport!r}")
        if self.autotune not in (None, "model", "measure"):
            raise ValueError(f"autotune must be model|measure|None, "
                             f"got {self.autotune!r}")


class _TiledRunner:
    """The ``tiled`` policy's runner: a per-image-length cache of
    :class:`~repro.core.tiling.TilePlan` objects (scratch-bank addresses
    are baked into the rewritten descriptors, so a plan is only valid for
    one image length — a Program's is fixed, raw descriptor calls may
    vary)."""

    def __init__(self, descs: Sequence[Descriptor], mem_spec: NtxMemSpec,
                 overlap: bool):
        self.descs = list(descs)
        self.mem_spec = mem_spec
        self.overlap = overlap
        self._plans: Dict[int, object] = {}
        self._last = None

    def __call__(self, mem) -> jnp.ndarray:
        from .tiling import TilePlan
        mem = jnp.asarray(mem, jnp.float32)
        plan = self._plans.get(mem.shape[0])
        if plan is None:
            plan = TilePlan(self.descs, self.mem_spec,
                            image_elems=mem.shape[0])
            self._plans[mem.shape[0]] = plan
        self._last = plan
        return plan.execute(mem, overlap=self.overlap)

    @property
    def stats(self) -> Optional[Dict]:
        return self._last.stats if self._last is not None else None


class Executor:
    """Policy-driven execution of NTX descriptor programs.

    ``Executor()`` runs with the default auto policy;
    ``Executor(ExecutionPolicy(...))`` or keyword overrides
    (``Executor(policy="pipeline", n_clusters=8)``) pin it down.
    ``stats`` after a run records the resolved policy, the gain ratios the
    auto decision consulted, and the underlying scheduler's stats.
    """

    def __init__(self, policy: "ExecutionPolicy | str | None" = None,
                 **overrides):
        if isinstance(policy, str):        # Executor(policy="pipeline")
            overrides = {"policy": policy, **overrides}
            policy = None
        if policy is None:
            policy = ExecutionPolicy(**overrides)
        elif overrides:
            policy = dataclasses.replace(policy, **overrides)
        self.policy = policy
        self.stats: Dict = {}

    # -- policy selection ----------------------------------------------
    def _n_clusters(self) -> int:
        if self.policy.n_clusters is not None:
            return max(1, int(self.policy.n_clusters))
        return max(1, len(jax.devices()))

    def _mem_spec(self) -> NtxMemSpec:
        if self.policy.mem is not None:
            return self.policy.mem
        return NtxMemSpec.from_cluster(self.policy.spec)

    def _autotune_mode(self) -> str:
        from repro.kernels import ops
        return self.policy.autotune or ops.get_autotune_mode()

    def select_policy(self, descs: Sequence[Descriptor]) -> tuple:
        """(chosen policy, gain dicts) for a descriptor program.

        The capacity model rules first: a working set larger than the
        cluster TCDM cannot faithfully run under any resident policy, so
        it tiles (``gains["tiling"]`` carries the verdict and the
        double-buffer roofline). Programs that fit are scored vs.
        one-command-at-a-time serial dispatch: ``fused`` scores the
        fusion speedup; the mesh policies price their scheduling gain on
        top of fused sub-streams, so their score is the product. The
        earliest (simplest) policy wins ties — an empty or indivisible
        program degrades gracefully to ``serial``/``fused``.
        """
        from repro.perfmodel import ntx as perfmodel
        gains = perfmodel.policy_gains(descs, n_clusters=self._n_clusters(),
                                       spec=self.policy.spec,
                                       setup_cycles=self.policy.setup_cycles,
                                       mem=self._mem_spec())
        fusion = gains["fusion"]["speedup"]
        scores = {"serial": 1.0,
                  "fused": fusion,
                  "multistream": fusion * gains["multistream"]["speedup"],
                  "pipeline": fusion * gains["pipeline"]["speedup"]}
        if not gains["tiling"]["fits"]:
            return "tiled", {"scores": scores, **gains}
        best = "serial"
        for cand in ("fused", "multistream", "pipeline"):
            if scores[cand] > scores[best] * (1.0 + _EPS):
                best = cand
        return best, {"scores": scores, **gains}

    def _race_policies(self, descs: Sequence[Descriptor],
                       mem: jnp.ndarray) -> tuple:
        """Measured auto policy: race the candidates once, keep the
        stopwatch's pick (the policy-level analogue of the GEMM-block
        ``autotune="measure"`` racing, memoized the same way). Each
        candidate is warmed once so compile/plan time stays out of the
        timed run. A candidate the compiler refuses (``ops.LOWERING_ERRORS``
        while its whole program is compiled once under ``jax.jit``) is
        recorded under ``"refused"``; if every candidate is refused, the
        last refusal is raised. Errors of a run propagate."""
        from repro.kernels import ops
        key = (tuple(descs), self._n_clusters(), self.policy.transport,
               self.policy.backend, self.policy.spec,
               self.policy.setup_cycles, self._mem_spec(),
               self.policy.dma_overlap)
        hit = _MEASURED_POLICY.get(key)
        if hit is not None:
            return hit["policy"], {"measured": dict(hit["times_s"]),
                                   "refused": dict(hit["refused"]),
                                   "measured_cached": True}
        times: Dict[str, float] = {}
        refused: Dict[str, str] = {}
        best, best_t, last_err = "serial", float("inf"), None
        for cand in ("serial", "fused", "multistream", "pipeline"):
            runner, _ = self._build_runner(descs, cand)
            _, err = ops.compile_or_refusal(runner, mem)
            if err is not None:
                refused[cand] = f"{type(err).__name__}: {err}"
                last_err = err
                continue
            jax.block_until_ready(runner(mem))        # warm: compile
            t0 = time.perf_counter()
            jax.block_until_ready(runner(mem))
            dt = time.perf_counter() - t0
            times[cand] = dt
            if dt < best_t:
                best, best_t = cand, dt
        if not times:
            raise last_err
        _MEASURED_POLICY[key] = {"policy": best, "times_s": times,
                                 "refused": refused}
        return best, {"measured": times, "refused": refused,
                      "measured_cached": False}

    def plan(self, program_or_descs) -> Dict:
        """Resolve the policy for a program without executing it."""
        descs = (program_or_descs.descriptors
                 if isinstance(program_or_descs, Program)
                 else list(program_or_descs))
        if self.policy.policy == "auto":
            chosen, gains = self.select_policy(descs)
        else:
            chosen, gains = self.policy.policy, None
        return {"policy": chosen, "n_clusters": self._n_clusters(),
                "transport": self.policy.transport, "gains": gains}

    # -- execution -----------------------------------------------------
    @contextlib.contextmanager
    def _env(self):
        """Apply the policy's backend/autotune for the duration of a run."""
        from repro.kernels import ops
        with contextlib.ExitStack() as stack:
            if (self.policy.backend is not None
                    and self.policy.backend != ops.get_backend()):
                stack.enter_context(ops.backend(self.policy.backend))
            if self.policy.autotune is not None:
                stack.enter_context(ops.autotune_mode(self.policy.autotune))
            yield

    def _build_runner(self, descs: Sequence[Descriptor], chosen: str):
        """The callable (mem -> mem) plus its stats source for one policy."""
        from .dispatch import dispatch
        from .multistream import ClusterScheduler, StageSchedule
        from .stream import CommandStream
        if chosen == "serial":
            def run(mem):
                for d in descs:
                    mem = dispatch(d, mem)
                return mem
            return run, None
        if chosen == "fused":
            cs = CommandStream(descs)
            return cs.execute, cs
        if chosen == "tiled":
            runner = _TiledRunner(descs, self._mem_spec(),
                                  self.policy.dma_overlap)
            return runner, runner
        cls = StageSchedule if chosen == "pipeline" else ClusterScheduler
        sched = cls(descs, n_clusters=self._n_clusters(),
                    spec=self.policy.spec,
                    setup_cycles=self.policy.setup_cycles)
        transport = self.policy.transport
        return (lambda mem: sched.execute(mem, transport)), sched

    def _resolve(self, descs: Sequence[Descriptor], policy: Optional[str],
                 mem: Optional[jnp.ndarray] = None) -> tuple:
        chosen = policy or self.policy.policy
        if chosen not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, "
                             f"got {chosen!r}")
        gains = None
        if chosen == "auto":
            chosen, gains = self.select_policy(descs)
            if (chosen != "tiled" and mem is not None
                    and self._autotune_mode() == "measure"):
                with self._env():
                    chosen, raced = self._race_policies(descs, mem)
                gains = {**(gains or {}), **raced}
        return chosen, gains

    def run_descriptors(self, descs: Sequence[Descriptor], mem,
                        policy: Optional[str] = None) -> jnp.ndarray:
        """Execute a raw descriptor list over a flat memory image.

        The raw-descriptor compatibility layer — new code should build a
        :class:`Program` and call :meth:`run`."""
        with span("ntx.run"):
            descs = list(descs)
            mem = jnp.asarray(mem, jnp.float32)
            chosen, gains = self._resolve(descs, policy, mem)
            runner, source = self._build_runner(descs, chosen)
            with span("ntx.execute"), self._env():
                out = runner(mem)
        self.stats = {"policy": chosen, "gains": gains,
                      "n_descriptors": len(descs),
                      "scheduler": getattr(source, "stats", None)}
        return out

    def run(self, program: Program, inputs=None,
            policy: Optional[str] = None) -> ProgramResult:
        """Pack, execute and unpack one program.

        ``inputs`` binds arrays to buffer handles/names (see
        :meth:`Program.pack`); ``policy`` overrides the executor's policy
        for this call (e.g. ``policy="pipeline"``). Returns a
        :class:`ProgramResult` — index it with the program's handles.

        Spans: ``ntx.run`` around ``ntx.pack``, ``ntx.plan`` (only when
        the program's plan is not cached, so its count is the misses),
        ``ntx.execute`` and ``ntx.unpack``.
        """
        with span("ntx.run"):
            descs = program.descriptors
            cache = getattr(program, "_plan_cache", None)
            if cache is None:
                cache = {}
                program._plan_cache = cache
            # cache the resolved policy AND its runner per program
            # version, so a steady-state loop neither re-prices nor
            # re-plans the program. backend/autotune are part of the key:
            # a jitted transport bakes the kernel backend in at trace
            # time, and measured autotune picks are only valid for the
            # mode they were raced under
            key = (program.version, policy or self.policy.policy,
                   self._n_clusters(), self.policy.transport,
                   self.policy.backend, self.policy.autotune,
                   self.policy.spec, self.policy.setup_cycles,
                   self._mem_spec(), self.policy.dma_overlap)
            with span("ntx.pack"):
                mem = program.pack(inputs)
            hit = cache.get(key)
            if hit is None:
                with span("ntx.plan"):
                    # plans for superseded program versions can never be
                    # reused
                    for stale in [k for k in cache
                                  if k[0] != program.version]:
                        del cache[stale]
                    chosen, gains = self._resolve(descs, policy, mem)
                    hit = (chosen, gains) + self._build_runner(descs, chosen)
                    cache[key] = hit
            chosen, gains, runner, source = hit
            with span("ntx.execute"), self._env():
                mem = runner(mem)
            self.stats = {"policy": chosen, "gains": gains,
                          "n_descriptors": len(descs),
                          "scheduler": getattr(source, "stats", None)}
            with span("ntx.unpack"):
                return program.unpack(mem)
