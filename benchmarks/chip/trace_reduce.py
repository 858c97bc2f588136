"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes into
plain Python tuples; ``reduce`` turns those into device busy time, device
time per operation and per compiled module, and the idle gaps, each gap
named by the harness's host span it falls in. Nothing here imports JAX at
module level, so the reduction can be tested on a synthetic trace without
loading any accelerator library.

Events are ``(name, start_ns, end_ns)``. A device plane holds two lines
of them: ``ops`` (each operation the device ran, from the ``XLA Ops``
line) and ``modules`` (each compiled program it ran, ``XLA Modules``).
Host spans are the harness's own ``TraceAnnotation`` ranges, whose names
start with ``SPAN_PREFIX``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

SPAN_PREFIX = "bench."
#: the span around the whole measured window
WINDOW_SPAN = "bench.window"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")
#: an op's name is its HLO text; the breakdown keeps its start
NAME_CHARS = 160


class Trace:
    """Device events per device plane, and the harness's host spans."""

    def __init__(self, devices: Dict[str, Dict[str, List[Event]]],
                 spans: List[Event]):
        self.devices = devices      # plane -> {"ops": [...], "modules": [...]}
        self.spans = spans


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {_OPS_LINE: "ops", _MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    lines[key].extend((e.name, e.start_ns, e.end_ns)
                                      for e in line.events)
            if lines["ops"] or lines["modules"]:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, spans)


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge possibly overlapping ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _window(trace: Trace) -> Tuple[float, float]:
    wins = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if wins:
        return min(s for s, _ in wins), max(e for _, e in wins)
    every = [(s, e) for lines in trace.devices.values()
             for evs in lines.values() for _, s, e in evs]
    if not every:
        raise ValueError("the trace holds no device event and no window")
    return min(s for s, _ in every), max(e for _, e in every)


def _span_at(spans: Sequence[Event], lo: float, hi: float) -> str:
    """The innermost harness span that covers most of ``[lo, hi)``."""
    best, best_key = WINDOW_SPAN, None
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        overlap = min(e, hi) - max(s, lo)
        if overlap <= 0:
            continue
        key = (overlap, -(e - s))      # most overlap, then the shortest
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """``(name, ns)`` per event less the time of the events nested in it
    (a loop op holds the ops of its body on the same line)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e - s for _, s, e in events]
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][0], own[i]) for i in range(len(events))]


def reduce(trace: Trace, top: int = 10) -> Dict:
    """Busy time, per-op and per-module device time, and idle gaps within
    the measured window (the ``bench.window`` span, else the extent of
    the device events).

    * ``busy_s``: union of the intervals in which an operation ran,
      averaged over the devices that ran any.
    * ``ops``: op name -> device seconds, summed over devices, less the
      time of the ops nested in it (a loop's body ops are its own).
    * ``modules``: module name (without its ``(id)``) -> seconds and
      count of executions, summed over devices.
    * ``gaps``: every idle interval of each device as ``(span, seconds)``,
      longest first, named by the host span it falls in.
    * ``breakdown``: the ``top`` operations by device time and the
      ``top`` longest gaps, as ``[[name, seconds], ...]``.
    """
    lo, hi = _window(trace)
    busy, gaps = [], []
    ops: Dict[str, float] = {}
    modules: Dict[str, Dict[str, float]] = {}
    for lines in trace.devices.values():
        evs = _clip(lines["ops"] or lines["modules"], lo, hi)
        if not evs:
            continue
        merged = union([(s, e) for _, s, e in evs])
        busy.append(sum(e - s for s, e in merged))
        cursor = lo
        for s, e in merged + [(hi, hi)]:
            if s > cursor:
                gaps.append((_span_at(trace.spans, cursor, s),
                             (s - cursor) * 1e-9))
            cursor = max(cursor, e)
        for name, ns in self_times(_clip(lines["ops"], lo, hi)):
            ops[name] = ops.get(name, 0.0) + ns * 1e-9
        for name, s, e in _clip(lines["modules"], lo, hi):
            m = modules.setdefault(_MODULE_ID.sub("", name),
                                   {"s": 0.0, "n": 0})
            m["s"] += (e - s) * 1e-9
            m["n"] += 1
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
        "n_devices": len(busy),
        "ops": ops,
        "modules": modules,
        "gaps": gaps,
        "breakdown": {"device_ops": [[n[:NAME_CHARS], s] for n, s in top_ops],
                      "idle_gaps": [[n, s] for n, s in gaps[:top]]},
    }


def op_seconds(reduced: Dict, pattern: str) -> Optional[float]:
    """Device seconds of the operations whose name matches ``pattern``
    (a regular expression); ``None`` when no operation matches."""
    rx = re.compile(pattern)
    hits = [s for name, s in reduced["ops"].items() if rx.search(name)]
    return sum(hits) if hits else None


def module_stats(reduced: Dict, pattern: str) -> Optional[Dict[str, float]]:
    """Summed seconds and executions of the modules matching ``pattern``;
    ``None`` when none matches."""
    rx = re.compile(pattern)
    hits = [m for name, m in reduced["modules"].items() if rx.search(name)]
    if not hits:
        return None
    return {"s": sum(m["s"] for m in hits), "n": sum(m["n"] for m in hits)}
