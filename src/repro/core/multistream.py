"""Multi-cluster stream scheduling: the paper's scaled-out machine.

The headline scaling claim (§III, Table II: 1 -> 8+ clusters) rests on many
NTX clusters executing descriptor streams concurrently, each hiding DMA
behind compute via double-buffered TCDM. The companion near-memory work
(arXiv:1803.04783) scales the same loosely-coupled clusters across DRAM
vaults, overlapping *dependent* stages through inter-cluster DMA.

This module builds that layer on top of ``core.stream``:

* :class:`StreamGraph` — dependency DAG over the AGUs' affine address
  ranges (``agu_span``/``spans_overlap``): descriptor j depends on an
  earlier descriptor i iff their accesses conflict (read-after-write,
  write-after-read or write-after-write). Read-read sharing — e.g. every
  layer streaming the same weights — creates no edge.
* :class:`SubStream` — a group of descriptors in program order, rebased
  into a compact local memory window with its own fused
  :class:`~repro.core.stream.CommandStream` (intra-stream fusion still
  applies) and a double-buffered DMA/compute roofline cost.
* :class:`ClusterScheduler` — the *independent* case: the DAG's connected
  components are provably order-free sub-streams, LPT-balanced onto an
  :class:`~repro.core.cluster.NtxClusterSpec`-derived mesh and executed
  concurrently (``shard_map`` over a "cluster" mesh axis, ``vmap``-stacked
  lanes on one device, or interleaved host execution).
* :class:`StageSchedule` — the *dependent* case: instead of collapsing a
  connected program back to one serial queue, the RAW/WAR/WAW edges are
  kept. Descriptors group into pipeline nodes by overlapping write
  footprints (SCC-condensed so the node graph is a DAG), the DAG is
  topologically level-ized into stages, each stage is handoff-aware
  LPT-balanced over the mesh (a consumer is biased toward its producer's
  cluster unless load imbalance outweighs the saved DMA) and executed
  concurrently, and every cross-stage edge is an explicit *handoff*: the
  producer's write span lands in the consumer
  cluster's rebased window through the shared L2 — the paper's
  inter-cluster DMA. Stage barriers preserve program order for every
  conflicting pair, so execution stays bit-equivalent to the serial
  stream.

Stages need not be *hard* barriers: ``execute(mem, mode="overlap")``
runs the §IV overlapped schedule — every stage's DMA-in (its nodes'
window gathers, which depend only on the pre-program image) is issued
before the previous stage's tail compute, handoffs stream
producer-window -> consumer-window directly, and all write-backs defer
to the end (legal because distinct pipeline nodes have disjoint write
hulls by construction). ``repro.perfmodel.ntx.pipeline_gain`` prices
both schedules.

``repro.core.Executor`` (``ExecutionPolicy(policy="pipeline",
transport=...)``) is the one-call entry point.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .cluster import NtxClusterSpec, PAPER_CLUSTER
from .descriptor import Descriptor
from .stream import (CommandStream, desc_spans, merge_spans, span_empty,
                     spans_overlap)

Span = Tuple[int, int]

_ELEM_BYTES = 4

# kept under the old private name for backward compatibility
_merge_spans = merge_spans


def _conflict(a_reads, a_write, b_reads, b_write) -> bool:
    """True iff the two descriptors must stay ordered (RAW/WAR/WAW)."""
    if spans_overlap(a_write, b_write):
        return True
    if any(spans_overlap(a_write, r) for r in b_reads):
        return True
    return any(spans_overlap(b_write, r) for r in a_reads)


def _intersect_bytes(a_spans: Sequence[Span], b_spans: Sequence[Span],
                     elem_bytes: int = _ELEM_BYTES) -> int:
    """Bytes in the intersection of two merged span lists."""
    return elem_bytes * sum(
        max(0, min(a_hi, b_hi) - max(a_lo, b_lo))
        for a_lo, a_hi in a_spans for b_lo, b_hi in b_spans)


# ----------------------------------------------------------------------
# Sub-streams
# ----------------------------------------------------------------------
@dataclasses.dataclass
class SubStream:
    """One node of the schedule (component or pipeline stage node), in
    program order.

    ``descs`` are the original descriptors; ``local`` the same descriptors
    rebased so the window [lo, hi) maps to local addresses [0, size).
    ``read_ranges``/``write_ranges`` are the merged global footprints the
    handoff planner sizes inter-cluster DMAs with.
    """

    indices: Tuple[int, ...]
    descs: List[Descriptor]
    lo: int
    hi: int
    write_ranges: List[Span]            # global, merged
    read_ranges: List[Span] = dataclasses.field(default_factory=list)
    local: List[Descriptor] = dataclasses.field(default_factory=list)
    stream: CommandStream = None

    @property
    def size(self) -> int:
        return self.hi - self.lo

    def roofline_time(self, spec: NtxClusterSpec = PAPER_CLUSTER,
                      setup_cycles: int = 100, overlap: bool = True) -> float:
        """Time on ONE cluster: double-buffered max(compute, dma) per fused
        group (overlap=False: the costs add — no DMA engine), plus the
        per-group offload setup the RISC-V pays."""
        flops = self.stream.flops()
        byts = self.stream.bytes_moved()
        tc = flops / spec.practical_flops
        td = byts / spec.practical_bw
        t = max(tc, td) if overlap else (tc + td)
        return t + setup_cycles / spec.ntx_freq_hz * len(self.stream.groups)


def _rebase(desc: Descriptor, lo: int) -> Descriptor:
    shift = lambda agu: dataclasses.replace(agu, base=agu.base - lo)
    kw = {"agu2": shift(desc.agu2)}
    if desc.reads_per_iter >= 1:
        kw["agu0"] = shift(desc.agu0)
    if desc.reads_per_iter >= 2:
        kw["agu1"] = shift(desc.agu1)
    return dataclasses.replace(desc, **kw)


# ----------------------------------------------------------------------
# Strongly-connected components (iterative Tarjan)
# ----------------------------------------------------------------------
def _tarjan_scc(n: int, succ: List[List[int]]) -> Tuple[List[int], int]:
    """Component id per node. Cycles in the preliminary node graph (write
    ping-pong across regions) must merge into one pipeline node."""
    index: List[Optional[int]] = [None] * n
    low = [0] * n
    onstk = [False] * n
    stk: List[int] = []
    comp = [0] * n
    counter = 0
    ncomp = 0
    for root in range(n):
        if index[root] is not None:
            continue
        work = [(root, 0)]
        while work:
            v, pi = work[-1]
            if pi == 0:
                index[v] = low[v] = counter
                counter += 1
                stk.append(v)
                onstk[v] = True
            advanced = False
            for i in range(pi, len(succ[v])):
                w = succ[v][i]
                if index[w] is None:
                    work[-1] = (v, i + 1)
                    work.append((w, 0))
                    advanced = True
                    break
                if onstk[w]:
                    low[v] = min(low[v], index[w])
            if advanced:
                continue
            work.pop()
            if low[v] == index[v]:
                while True:
                    w = stk.pop()
                    onstk[w] = False
                    comp[w] = ncomp
                    if w == v:
                        break
                ncomp += 1
            if work:
                u = work[-1][0]
                low[u] = min(low[u], low[v])
    return comp, ncomp


# ----------------------------------------------------------------------
# The DAG
# ----------------------------------------------------------------------
class StreamGraph:
    """Dependency DAG over a descriptor program's AGU address ranges."""

    def __init__(self, descs: Sequence[Descriptor]):
        self.descs = list(descs)
        spans = [desc_spans(d) for d in self.descs]
        n = len(self.descs)
        self.edges: List[Tuple[int, int]] = []
        parent = list(range(n))

        def find(i):
            while parent[i] != i:
                parent[i] = parent[parent[i]]
                i = parent[i]
            return i

        for j in range(n):
            rj, wj = spans[j]
            for i in range(j):
                ri, wi = spans[i]
                if _conflict(ri, wi, rj, wj):
                    self.edges.append((i, j))
                    parent[find(i)] = find(j)
        self._roots = [find(i) for i in range(n)]
        self._spans = spans

    @property
    def n_edges(self) -> int:
        return len(self.edges)

    def _make_substream(self, idxs: Sequence[int]) -> SubStream:
        descs = [self.descs[i] for i in idxs]
        touched: List[Span] = []
        writes: List[Span] = []
        reads: List[Span] = []
        for i in idxs:
            r, w = self._spans[i]
            reads.extend(r)
            writes.append(w)
            touched.extend(r)
            touched.append(w)
        touched = [s for s in touched if not span_empty(s)]
        lo = min((s[0] for s in touched), default=0)
        hi = max((s[1] for s in touched), default=0)
        sub = SubStream(indices=tuple(idxs), descs=descs, lo=lo, hi=hi,
                        write_ranges=merge_spans(writes),
                        read_ranges=merge_spans(reads))
        sub.local = [_rebase(d, lo) for d in descs]
        sub.stream = CommandStream(sub.local)
        return sub

    def partition(self) -> List[SubStream]:
        """Fully independent sub-streams (connected components),
        deterministically ordered by the index of their first descriptor;
        each keeps program order internally."""
        comps: dict = {}
        for i, r in enumerate(self._roots):
            comps.setdefault(r, []).append(i)
        return [self._make_substream(idxs)
                for idxs in sorted(comps.values(), key=lambda ix: ix[0])]

    def pipeline_partition(self) -> Tuple[List[SubStream],
                                          List[Tuple[int, int]]]:
        """Pipeline nodes + node-level dependency edges.

        Descriptors whose *write* footprints overlap form one node (an
        in-place chain, an accumulator region); descriptor conflicts lift
        to node edges; cyclic node groups (region ping-pong) SCC-condense
        into a single node so the result is a DAG. Nodes are ordered by
        first descriptor index and keep program order internally; every
        descriptor-level conflict is represented by a node edge or falls
        inside one node."""
        n = len(self.descs)
        parent = list(range(n))

        def find(i):
            while parent[i] != i:
                parent[i] = parent[parent[i]]
                i = parent[i]
            return i

        # every write-write overlap is already a WAW conflict edge, so the
        # grouping relation is a filter over self.edges, not a fresh
        # all-pairs scan
        for i, j in self.edges:
            if spans_overlap(self._spans[i][1], self._spans[j][1]):
                parent[find(i)] = find(j)
        groups: dict = {}
        for i in range(n):
            groups.setdefault(find(i), []).append(i)
        prelim = sorted(groups.values(), key=lambda ix: ix[0])
        node_of = {}
        for gi, idxs in enumerate(prelim):
            for i in idxs:
                node_of[i] = gi
        succ: List[List[int]] = [[] for _ in prelim]
        seen = set()
        for i, j in self.edges:
            u, v = node_of[i], node_of[j]
            if u != v and (u, v) not in seen:
                seen.add((u, v))
                succ[u].append(v)
        comp, _ = _tarjan_scc(len(prelim), succ)
        merged: dict = {}
        for gi, idxs in enumerate(prelim):
            merged.setdefault(comp[gi], []).extend(idxs)
        final = sorted((sorted(ix) for ix in merged.values()),
                       key=lambda ix: ix[0])
        node_id = {}
        for fi, idxs in enumerate(final):
            for i in idxs:
                node_id[i] = fi
        nodes = [self._make_substream(idxs) for idxs in final]
        nedges = sorted({(node_id[i], node_id[j]) for i, j in self.edges
                         if node_id[i] != node_id[j]})
        return nodes, nedges


# ----------------------------------------------------------------------
# Load balancing
# ----------------------------------------------------------------------
def _lpt_assign(costs: Sequence[float], n_clusters: int) -> List[int]:
    """Longest-processing-time-first onto the least-loaded cluster.

    Deterministic: ties broken by sub-stream index, then cluster index.
    Always a valid partition: every sub-stream lands on a cluster in
    [0, n_clusters), including when ``n_clusters`` exceeds the number of
    sub-streams or costs are 0 (extra clusters simply stay empty)."""
    n_clusters = max(1, int(n_clusters))
    order = sorted(range(len(costs)), key=lambda i: (-costs[i], i))
    load = [0.0] * n_clusters
    assign = [0] * len(costs)
    for i in order:
        c = min(range(n_clusters), key=lambda k: (load[k], k))
        assign[i] = c
        load[c] += costs[i]
    return assign


# ----------------------------------------------------------------------
# Shared sub-stream executors
# ----------------------------------------------------------------------
def _substreams_uniform(subs: Sequence[SubStream]) -> bool:
    """All sub-streams share one rebased program (and window size) — the
    data-parallel-clusters case the paper scales: one kernel, per-cluster
    data tiles. Only then can the lanes stack for vmap/shard_map."""
    if not subs:
        return False
    first = subs[0]
    return all(s.size == first.size and s.local == first.local
               for s in subs[1:])


def _substreams_traceable(subs: Sequence[SubStream]) -> bool:
    from .dispatch import traceable_descriptor
    return all(traceable_descriptor(d) for s in subs for d in s.local)


def _run_interleaved(mem: jnp.ndarray,
                     subs: Sequence[SubStream]) -> Tuple[jnp.ndarray, int]:
    """Round-robin over sub-streams at fused-group granularity — the host
    stands in for the per-cluster DMA engines, issuing one group per
    cluster per turn. The sub-streams must be mutually independent, so any
    interleaving is bit-identical to serial execution. Returns the updated
    memory and the number of turns."""
    windows = [mem[s.lo:s.hi] for s in subs]
    stats = [s.stream._fresh_stats() for s in subs]
    cursors = [0] * len(subs)
    done = 0
    while done < len(subs):
        done = 0
        for i, sub in enumerate(subs):
            groups = sub.stream.groups
            if cursors[i] >= len(groups):
                done += 1
                continue
            windows[i] = groups[cursors[i]].run(windows[i], stats[i])
            cursors[i] += 1
    for sub, w in zip(subs, windows):
        for glo, ghi in sub.write_ranges:
            mem = mem.at[glo:ghi].set(w[glo - sub.lo:ghi - sub.lo])
    return mem, max((len(s.stream.groups) for s in subs), default=0)


def _stacked_run_fn(subs: Sequence[SubStream], sharded: bool,
                    stats: Optional[dict] = None):
    """One jitted computation over uniform, traceable sub-streams: gather
    lanes, run the shared rebased program on every lane (vmap, optionally
    sharded over the "cluster" mesh axis), scatter the write ranges back —
    no per-stream dispatch round trips."""
    groups = subs[0].stream.groups

    def body(window):
        st = subs[0].stream._fresh_stats()
        for g in groups:
            window = g.run(window, st)
        return window

    n_lanes = len(subs)
    if sharded:
        from jax.sharding import PartitionSpec as P
        from repro.distributed.mesh import make_mesh
        n_dev = min(len(jax.devices()), n_lanes)
        if stats is not None:
            stats["n_devices_used"] = n_dev
        mesh = make_mesh((n_dev,), ("cluster",))
        pad = (-n_lanes) % n_dev
        # check_vma off: a Pallas kernel in the lane body does not declare
        # how its outputs vary over the mesh axis (each lane is local)
        inner = jax.shard_map(lambda w: jax.vmap(body)(w), mesh=mesh,
                              in_specs=(P("cluster"),),
                              out_specs=P("cluster"), check_vma=False)
    else:
        pad = 0
        inner = jax.vmap(body)

    def run(m):
        lanes = jnp.stack([m[s.lo:s.hi] for s in subs])
        if pad:
            lanes = jnp.concatenate(
                [lanes, jnp.zeros((pad, lanes.shape[1]), lanes.dtype)])
        out = inner(lanes)
        for i, sub in enumerate(subs):
            for glo, ghi in sub.write_ranges:
                m = m.at[glo:ghi].set(out[i, glo - sub.lo:ghi - sub.lo])
        return m

    return jax.jit(run)


# ----------------------------------------------------------------------
# The scheduler: independent components
# ----------------------------------------------------------------------
class ClusterScheduler:
    """Maps a program's independent sub-streams onto a cluster mesh.

    Execution modes (``execute(mem, mode=...)``):

    * ``"shard_map"`` — stacked windows sharded over a 1-D "cluster" device
      mesh (``jax.shard_map``); each device runs its lanes'
      shared program. Requires uniform + traceable sub-streams, >= 2 devices.
    * ``"vmap"``      — the same stacked body batched on one device: the
      lanes execute as ONE fused computation (overlapped, no per-stream
      dispatch round trips). Requires uniform + traceable.
    * ``"interleave"``— host fallback, always legal: sub-streams execute on
      their local windows round-robin at fused-group granularity (the
      single-device analogue of the per-cluster DMA interleave).
    * ``"serial"``    — one CommandStream over the whole program (oracle).
    * ``"auto"``      — shard_map if legal and >= 2 devices, else interleave.

    Every mode is bit-equivalent to serial execution for elementwise
    programs and numerically equivalent (same-kernel, different batching)
    otherwise; independence of the partition guarantees order freedom.
    """

    def __init__(self, descs_or_graph, n_clusters: Optional[int] = None,
                 spec: NtxClusterSpec = PAPER_CLUSTER,
                 setup_cycles: int = 100):
        self.graph = (descs_or_graph if isinstance(descs_or_graph, StreamGraph)
                      else StreamGraph(descs_or_graph))
        self.spec = spec
        self.substreams = self.graph.partition()
        if n_clusters is None:
            n_clusters = max(1, len(jax.devices()))
        self.n_clusters = max(1, int(n_clusters))
        self.costs = [s.roofline_time(spec, setup_cycles)
                      for s in self.substreams]
        self.assignment = _lpt_assign(self.costs, self.n_clusters)
        self._jitted = {}
        self.stats = {
            "n_descriptors": len(self.graph.descs),
            "n_substreams": len(self.substreams),
            "n_edges": self.graph.n_edges,
            "n_clusters": self.n_clusters,
            "assignment": list(self.assignment),
            "uniform": self.uniform(),
            "traceable": self.traceable(),
            "cluster_times_s": self.cluster_times(),
            "critical_path_s": max(self.cluster_times(), default=0.0),
            "serial_time_s": sum(self.costs),
            "mode_used": None,
        }

    # -- analysis ------------------------------------------------------
    def cluster_times(self) -> List[float]:
        t = [0.0] * self.n_clusters
        for cost, c in zip(self.costs, self.assignment):
            t[c] += cost
        return t

    def model_speedup(self) -> float:
        crit = max(self.cluster_times(), default=0.0) if self.costs else 0.0
        return sum(self.costs) / crit if crit > 0 else 1.0

    def uniform(self) -> bool:
        return _substreams_uniform(self.substreams)

    def traceable(self) -> bool:
        return _substreams_traceable(self.substreams)

    def plan_mode(self, mode: str = "auto") -> str:
        if mode == "overlap":
            # stage overlap is a pipeline concept; independent sub-streams
            # have no stage boundaries, so fall back to the best transport
            mode = "auto"
        if mode != "auto":
            return mode
        if self.uniform() and self.traceable():
            if len(jax.devices()) >= 2 and len(self.substreams) >= 2:
                return "shard_map"
            return "vmap"
        return "interleave"

    # -- execution -----------------------------------------------------
    def execute(self, mem, mode: str = "auto") -> jnp.ndarray:
        mem = jnp.asarray(mem, jnp.float32)
        mode = self.plan_mode(mode)
        self.stats["mode_used"] = mode
        if mode == "serial":
            return CommandStream(self.graph.descs).execute(mem)
        if mode == "interleave":
            mem, turns = _run_interleaved(mem, self.substreams)
            self.stats["interleave_turns"] = turns
            return mem
        if mode in ("vmap", "shard_map"):
            if not (self.uniform() and self.traceable()):
                raise ValueError(
                    f"mode {mode!r} needs uniform, traceable sub-streams "
                    "(use mode='interleave' or 'auto')")
            key = "shard" if mode == "shard_map" else "vmap"
            if key not in self._jitted:
                self._jitted[key] = _stacked_run_fn(
                    self.substreams, sharded=(mode == "shard_map"),
                    stats=self.stats)
            return self._jitted[key](mem)
        raise ValueError(f"unknown mode {mode!r}")


# ----------------------------------------------------------------------
# The pipeline: dependent stages with inter-cluster handoffs
# ----------------------------------------------------------------------
class StageSchedule:
    """Stage-level pipeline schedule for DEPENDENT descriptor programs.

    ``pipeline_partition`` keeps the dependency edges instead of
    collapsing connected components to one queue: nodes level-ize
    topologically into stages; nodes inside one stage are mutually
    conflict-free (any conflict forces different levels) and execute
    concurrently with the same transports as :class:`ClusterScheduler`;
    stage barriers plus write-back through the shared memory realise every
    cross-stage handoff (the paper's inter-cluster DMA through L2), so
    every execution mode stays bit-equivalent to the serial stream.

    ``execute(mem, mode=...)`` takes a per-stage transport *preference*:
    ``"vmap"``/``"shard_map"`` stack a stage's lanes when that stage is
    uniform + traceable and falls back to interleaved host execution
    otherwise; ``"interleave"`` always interleaves; ``"serial"`` is the
    one-queue oracle; ``"auto"`` picks shard_map on >= 2 devices.
    """

    def __init__(self, descs_or_graph, n_clusters: Optional[int] = None,
                 spec: NtxClusterSpec = PAPER_CLUSTER,
                 setup_cycles: int = 100):
        self.graph = (descs_or_graph if isinstance(descs_or_graph, StreamGraph)
                      else StreamGraph(descs_or_graph))
        self.spec = spec
        self.setup_cycles = setup_cycles
        self.nodes, self.node_edges = self.graph.pipeline_partition()
        if n_clusters is None:
            n_clusters = max(1, len(jax.devices()))
        self.n_clusters = max(1, int(n_clusters))

        n = len(self.nodes)
        succs: List[List[int]] = [[] for _ in range(n)]
        indeg = [0] * n
        for u, v in self.node_edges:
            succs[u].append(v)
            indeg[v] += 1
        self.level = [0] * n
        q = deque(i for i in range(n) if indeg[i] == 0)
        seen = 0
        while q:
            u = q.popleft()
            seen += 1
            for v in succs[u]:
                self.level[v] = max(self.level[v], self.level[u] + 1)
                indeg[v] -= 1
                if indeg[v] == 0:
                    q.append(v)
        assert seen == n, "pipeline_partition must produce a DAG"
        n_stages = (max(self.level) + 1) if n else 0
        self.stages: List[List[int]] = [[] for _ in range(n_stages)]
        for i in range(n):
            self.stages[self.level[i]].append(i)

        self.costs = [nd.roofline_time(spec, setup_cycles)
                      for nd in self.nodes]
        # Per-edge handoff sizing first: the producer's write spans
        # restricted to the consumer's read footprint are the bytes the
        # inter-cluster DMA moves. The stage LPT below needs them.
        self._edge_bytes = {
            (u, v): _intersect_bytes(self.nodes[u].write_ranges,
                                     self.nodes[v].read_ranges)
            for u, v in self.node_edges}
        in_edges: Dict[int, List[Tuple[int, int]]] = {}
        for (u, v), nbytes in self._edge_bytes.items():
            in_edges.setdefault(v, []).append((u, nbytes))
        self._in_edges = in_edges

        # Handoff-aware stage LPT: nodes go longest-first onto the cluster
        # minimising (stage load + the DMA a non-co-located placement
        # would pay). Producers live in strictly earlier stages, so their
        # clusters are already fixed when a consumer is placed; a consumer
        # landing on its producer's cluster hands off through the
        # cluster's own TCDM for free.
        bw = spec.practical_bw
        self.assignment = [0] * n
        for stage in self.stages:
            load = [0.0] * self.n_clusters
            for i in sorted(stage, key=lambda j: (-self.costs[j], j)):
                def placed_cost(k: int) -> float:
                    dma = sum(nb / bw for u, nb in in_edges.get(i, ())
                              if self.assignment[u] != k)
                    return load[k] + dma
                c = min(range(self.n_clusters),
                        key=lambda k: (placed_cost(k), load[k], k))
                self.assignment[i] = c
                load[c] += self.costs[i]

        self.handoffs: List[Dict] = []
        for u, v in self.node_edges:
            self.handoffs.append({
                "src": u, "dst": v, "bytes": self._edge_bytes[(u, v)],
                "cross_cluster": self.assignment[u] != self.assignment[v],
                "stage": self.level[v]})

        self._jitted = {}
        self.stats = {
            "n_descriptors": len(self.graph.descs),
            "n_nodes": n,
            "n_edges": len(self.node_edges),
            "n_stages": n_stages,
            "n_clusters": self.n_clusters,
            "levels": list(self.level),
            "assignment": list(self.assignment),
            "stage_sizes": [len(s) for s in self.stages],
            "handoff_bytes": sum(h["bytes"] for h in self.handoffs),
            "handoff_bytes_cross": sum(h["bytes"] for h in self.handoffs
                                       if h["cross_cluster"]),
            "serial_time_s": sum(self.costs),
            "pipeline_time_s": self.model_time(),
            "pipeline_overlap_time_s": self.model_time(overlap=True),
            "stage_times_s": self.stage_times(),
            "mode_used": None,
        }

    # -- analysis ------------------------------------------------------
    def stage_times(self) -> List[float]:
        """Per-stage critical path: the most-loaded cluster of each stage."""
        out = []
        for stage in self.stages:
            load = [0.0] * self.n_clusters
            for i in stage:
                load[self.assignment[i]] += self.costs[i]
            out.append(max(load))
        return out

    def handoff_time(self) -> float:
        """DMA time of the cross-cluster handoffs at the practical rate."""
        nbytes = sum(h["bytes"] for h in self.handoffs if h["cross_cluster"])
        return nbytes / self.spec.practical_bw

    def overlap_handoff_time(self) -> float:
        """Cross-cluster handoff DMA *not* hidden by the overlapped
        schedule. A handoff u -> v can start the moment u finishes and
        stream while u's stage still runs its critical path, so the
        hidden budget per edge is the producer stage's slack after u:
        ``stage_t[level(u)] - cost(u)``. Only the excess is exposed —
        the §IV "DMA-in of stage s+1 under stage s's tail compute"."""
        bw = self.spec.practical_bw
        stage_t = self.stage_times()
        exposed = 0.0
        for h in self.handoffs:
            if not h["cross_cluster"]:
                continue
            u = h["src"]
            slack = max(0.0, stage_t[self.level[u]] - self.costs[u])
            exposed += max(0.0, h["bytes"] / bw - slack)
        return exposed

    def model_time(self, overlap: bool = False) -> float:
        """Pipelined time: sum of stage critical paths + handoff DMA
        (all of it under the barrier schedule, only the un-hidden excess
        under the overlapped one)."""
        handoff = (self.overlap_handoff_time() if overlap
                   else self.handoff_time())
        return sum(self.stage_times()) + handoff

    def model_speedup(self, overlap: bool = False) -> float:
        t = self.model_time(overlap)
        return sum(self.costs) / t if t > 0 else 1.0

    def plan_stage_mode(self, stage: Sequence[int], mode: str = "auto") -> str:
        if mode == "interleave":
            return "interleave"
        subs = [self.nodes[i] for i in stage]
        if (len(subs) >= 2 and _substreams_uniform(subs)
                and _substreams_traceable(subs)):
            if mode in ("vmap", "shard_map"):
                return mode
            return ("shard_map" if len(jax.devices()) >= 2 else "vmap")
        return "interleave"

    # -- execution -----------------------------------------------------
    def _execute_overlap(self, mem: jnp.ndarray) -> jnp.ndarray:
        """The §IV overlapped schedule (no hard stage barriers).

        Every node's base window gathers from the PRE-program image —
        the next stage's DMA-in is issued before the current stage's
        tail compute, which the functional data flow then allows to
        overlap. Dependent data moves producer-window ->
        consumer-window (the inter-cluster DMA through L2) instead of
        round-tripping through a global barrier write-back, and all
        write-backs defer to the end — legal because distinct pipeline
        nodes have disjoint write hulls (write-overlap grouping), so
        they commute. Bit-equal to the barrier schedule: consumers see
        exactly the producer spans they saw before, everything else
        comes from the untouched original image.
        """
        windows: Dict[int, jnp.ndarray] = {}
        for i in self.stages[0] if self.stages else []:
            nd = self.nodes[i]
            windows[i] = mem[nd.lo:nd.hi]
        for si, stage in enumerate(self.stages):
            if si + 1 < len(self.stages):
                # stage s+1's DMA-in, issued before stage s computes
                for i in self.stages[si + 1]:
                    nd = self.nodes[i]
                    windows[i] = mem[nd.lo:nd.hi]
            for i in stage:
                nd = self.nodes[i]
                w = windows[i]
                for u, _ in self._in_edges.get(i, ()):
                    und = self.nodes[u]
                    for lo, hi in und.write_ranges:
                        plo, phi = max(lo, nd.lo), min(hi, nd.hi)
                        if plo < phi:
                            w = w.at[plo - nd.lo:phi - nd.lo].set(
                                windows[u][plo - und.lo:phi - und.lo])
                st = nd.stream._fresh_stats()
                for g in nd.stream.groups:
                    w = g.run(w, st)
                windows[i] = w
        for i, nd in enumerate(self.nodes):
            for lo, hi in nd.write_ranges:
                mem = mem.at[lo:hi].set(windows[i][lo - nd.lo:hi - nd.lo])
        return mem

    def execute(self, mem, mode: str = "auto") -> jnp.ndarray:
        mem = jnp.asarray(mem, jnp.float32)
        if mode == "serial":
            self.stats["mode_used"] = "serial"
            return CommandStream(self.graph.descs).execute(mem)
        if mode == "overlap":
            self.stats["mode_used"] = "overlap"
            self.stats["stage_modes"] = ["overlap"] * len(self.stages)
            return self._execute_overlap(mem)
        if mode not in ("auto", "vmap", "shard_map", "interleave"):
            raise ValueError(f"unknown mode {mode!r}")
        stage_modes = []
        for si, stage in enumerate(self.stages):
            m = self.plan_stage_mode(stage, mode)
            stage_modes.append(m)
            subs = [self.nodes[i] for i in stage]
            if m == "interleave":
                mem, _ = _run_interleaved(mem, subs)
            else:
                key = (si, m)
                if key not in self._jitted:
                    self._jitted[key] = _stacked_run_fn(
                        subs, sharded=(m == "shard_map"), stats=self.stats)
                mem = self._jitted[key](mem)
        self.stats["mode_used"] = mode
        self.stats["stage_modes"] = stage_modes
        return mem
