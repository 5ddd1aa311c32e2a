"""Reduction of a profiler trace (``.xplane.pb``) to device time.

Read with ``jax.profiler.ProfileData``.  Device planes are
``/device:TPU:<i>``; on each, the ``XLA Modules`` line holds one event per
program execution (``jit__decode_core(7)``: program name and id) and the
``XLA Ops`` line one event per operation, kernels included, named by its
HLO text (``%fusion.3 = bf16[...] fusion(...)``: the operation's name is
what precedes `` = ``).  Host planes
hold the harness's spans (``TraceAnnotation``), among them ``window``,
which marks the traced window.

Programs and operations count where they start inside the window.  A
device is busy where any of its operations runs: the union of the
``XLA Ops`` intervals (of the ``XLA Modules`` ones where a trace has no
op line), clipped to the window.  Every stretch of the window in which a
device is not busy is an idle gap, labelled by the harness span that
overlaps it most (``none`` where no span does).  Host and device
timestamps share one timeline in the trace, to about a millisecond.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
SUFFIX = re.compile(r"\(\d+\)$")
OP = re.compile(r"^%?([^\s=]+)")
#: the harness's host spans (``bench/drive.py``, ``bench/run.py``)
SPANS = ("step", "submit", "admit", "dispatch_decode", "materialize")
LABELLED = 40          # the longest gaps that get a label
INSTANCE = re.compile(r"\.\d+$")
CONTROL_FLOW = ("while", "conditional", "call")


@dataclass
class Summary:
    window_s: float
    devices: List[int]
    busy_s: Dict[int, float]
    programs: Dict[str, List[float]] = field(default_factory=dict)
    ops: Dict[str, List[float]] = field(default_factory=dict)
    gaps: List[Tuple[float, str, int]] = field(default_factory=list)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def program_time(self, names) -> Tuple[float, int]:
        """Seconds and executions of the programs named (all devices)."""
        s = n = 0
        for name in names:
            t, c = self.programs.get(name, (0.0, 0))
            s, n = s + t, n + c
        return s, n

    def op_time(self, patterns) -> Tuple[float, int]:
        """Seconds and executions of the operations whose names match any
        of the regular expressions (all devices)."""
        rx = [re.compile(p) for p in patterns]
        s = n = 0
        for name, (t, c) in self.ops.items():
            if any(r.search(name) for r in rx):
                s, n = s + t, n + c
        return s, n


def find(directory: str) -> str:
    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(path: str, *, window: str = "window") -> Summary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    dev_lines = {}
    for plane in pd.planes:
        m = DEVICE.match(plane.name)
        if m:
            dev_lines[int(m.group(1))] = {ln.name: list(ln.events)
                                          for ln in plane.lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    win = [(a, b) for a, b, n in spans if n == window]
    if not win:
        raise ValueError(f"the trace holds no {window!r} span")
    lo, hi = win[0]
    harness = [(a, b, n) for a, b, n in spans if n in SPANS]
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    ops: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    busy, gaps = {}, []
    for dev, lines in sorted(dev_lines.items()):
        for e in lines.get("XLA Modules", []):
            if lo <= e.start_ns < hi:
                p = programs[SUFFIX.sub("", e.name)]
                p[0] += e.duration_ns * 1e-9
                p[1] += 1
        for e in lines.get("XLA Ops", []):
            if lo <= e.start_ns < hi:
                o = ops[op_name(e.name)]
                o[0] += e.duration_ns * 1e-9
                o[1] += 1
        src = lines.get("XLA Ops") or lines.get("XLA Modules", [])
        iv = _clip(union([(e.start_ns, e.start_ns + e.duration_ns)
                          for e in src]), lo, hi)
        busy[dev] = sum(b - a for a, b in iv) * 1e-9
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, dev))
    gaps.sort(reverse=True)
    labelled = [(g * 1e-9, _label(a, a + g, harness), dev)
                for g, a, dev in gaps[:LABELLED]]
    return Summary((hi - lo) * 1e-9, sorted(dev_lines), busy,
                   dict(programs), dict(ops), labelled)


def op_name(text: str) -> str:
    m = OP.match(text)
    return m.group(1) if m else text


def _label(a, b, spans) -> str:
    best, name = 0.0, "none"
    for s, e, n in spans:
        over = min(b, e) - max(a, s)
        if over > best:
            best, name = over, n
    return name


def breakdown(summary: Summary, count: int = 10) -> dict:
    """The operations that took most device time and the longest idle
    gaps with what the host was doing, at most ``count`` of each.

    Operations are summed over their instances (``fusion.3`` and
    ``fusion.7`` are ``fusion``); loops and conditionals are left out,
    since the operations inside them are listed themselves."""
    by_name: Dict[str, float] = defaultdict(float)
    for name, (t, _) in summary.ops.items():
        base = INSTANCE.sub("", name)
        if base not in CONTROL_FLOW:
            by_name[base] += t
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:count]
    return {"device_ops": [[n, t] for n, t in top],
            "idle_gaps": [[f"{label} (device {dev})", s]
                          for s, label, dev in summary.gaps[:count]]}
