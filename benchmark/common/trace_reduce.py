"""From a ``jax.profiler`` trace to device metrics (on-chip-measurement
guide, sections 4 and 6).

``load`` reads the ``.xplane.pb`` the profiler writes with JAX's own
``ProfileData``: the device planes' op and module events, and the host
annotations the benchmark wrote. The reduction works on plain
``(start_ns, end_ns, name)`` tuples, so a test can check it on intervals
worked out by hand:

- busy time is the union of the op intervals of each device, inside the
  window, averaged over the devices;
- idle gaps are the stretches of the window that no op covers;
- each gap is labelled by the program span that overlaps it most (the
  deepest one on a tie), or ``host (no span)``.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float, str]  # start_ns, end_ns, name

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, device_plane=DEVICE_PLANE, annotation_prefix: str = "bench.") -> dict:
    """{"ops": {plane: [Interval]}, "modules": {plane: [Interval]},
    "annotations": [Interval]} of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = defaultdict(list)
    modules: Dict[str, List[Interval]] = defaultdict(list)
    notes: List[Interval] = []
    for plane in data.planes:
        if device_plane.fullmatch(plane.name):
            for line in plane.lines:
                dest = ops if line.name == OPS_LINE else modules if line.name == MODULES_LINE else None
                if dest is None:
                    continue
                for ev in line.events:
                    dest[plane.name].append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(annotation_prefix):
                        notes.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return {"ops": dict(ops), "modules": dict(modules), "annotations": notes}


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for s, e, n in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, n))
    return out


def union(intervals: Sequence[Interval]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals: Sequence[Interval], lo: float, hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Tuple[float, float], spans: Sequence[Tuple[float, float, str, int]]) -> str:
    """The span (start, end, name, depth) overlapping ``gap`` most; the
    deeper one wins a tie."""
    return _Labeller(spans)(gap)


class _Labeller:
    """``label`` over many gaps: the spans as arrays, once."""

    def __init__(self, spans):
        self.names = [n for _, _, n, _ in spans]
        self.s = np.array([s for s, _, _, _ in spans], dtype=np.float64)
        self.e = np.array([e for _, e, _, _ in spans], dtype=np.float64)
        self.d = np.array([d for _, _, _, d in spans], dtype=np.float64)

    def __call__(self, gap) -> str:
        if not self.names:
            return "host (no span)"
        ov = np.minimum(self.e, gap[1]) - np.maximum(self.s, gap[0])
        if ov.max() <= 0:
            return "host (no span)"
        best = ov == ov.max()
        i = int(np.flatnonzero(best)[np.argmax(self.d[best])])
        return self.names[i]


def window_of(annotations: Sequence[Interval]) -> Tuple[float, float]:
    ws = [(s, e) for s, e, n in annotations if n == WINDOW]
    if not ws:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    return ws[0]


def reduce(trace: dict, spans: Sequence[Tuple[float, float, str, int]] = (),
           top: int = 10) -> dict:
    """busy_s and window_s (busy averaged over the device planes), the
    device time of each op and module inside the window, the top ops
    and the idle seconds by the span that covers them."""
    lo, hi = window_of(trace["annotations"])
    planes = sorted(trace["ops"] or trace["modules"])
    if not planes:
        return {"busy_s": 0.0, "window_s": (hi - lo) / 1e9, "op_s": {}, "module_s": {},
                "device_ops": [], "idle_gaps": []}
    src = trace["ops"] or trace["modules"]
    busy = sum(busy_ns(src[p], lo, hi) for p in planes) / len(planes)
    op_s: Dict[str, float] = defaultdict(float)
    for p in planes:
        for s, e, n in clip(trace["ops"].get(p, []), lo, hi):
            op_s[n] += (e - s) / 1e9
    module_s: Dict[str, float] = defaultdict(float)
    for p in planes:
        for s, e, n in clip(trace["modules"].get(p, []), lo, hi):
            module_s[n] += (e - s) / 1e9
    idle: Dict[str, float] = defaultdict(float)
    labeller = _Labeller(spans)
    for p in planes:
        for g in gaps(src[p], lo, hi):
            idle[labeller(g)] += (g[1] - g[0]) / 1e9 / len(planes)
    by_time = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "busy_s": busy / 1e9,
        "window_s": (hi - lo) / 1e9,
        "op_s": dict(op_s),
        "module_s": dict(module_s),
        "device_ops": by_time(module_s or op_s)[:top],
        "idle_gaps": by_time(idle)[:top],
    }


def program_spans(traces, mono_to_ns) -> List[Tuple[float, float, str, int]]:
    """The program's own spans (``telemetry.trace.QueryTrace`` trees,
    monotonic seconds) on the trace's clock, each once."""
    seen, out = set(), []

    def walk(span, depth):
        if id(span) in seen:
            return
        seen.add(id(span))
        if span.t1 is not None:
            out.append((mono_to_ns(span.t0), mono_to_ns(span.t1), span.name, depth))
        for c in list(span.children):
            walk(c, depth + 1)

    for t in traces:
        walk(t.root, 0)
    return out


def span_durations(traces) -> Dict[str, List[float]]:
    """Seconds of every closed span, by name, each span once."""
    seen, out = set(), defaultdict(list)
    for t in traces:
        stack = [t.root]
        while stack:
            s = stack.pop()
            if id(s) in seen:
                continue
            seen.add(id(s))
            if s.t1 is not None:
                out[s.name].append(s.t1 - s.t0)
            stack.extend(list(s.children))
    return dict(out)

