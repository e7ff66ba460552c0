"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, idle
shares, kernel times and attributed idle gaps.

Device operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  Busy is the union of their intervals; copies
between host and device are not on that line, so a transfer alone counts
as idle.  The ``XLA Modules`` line names the compiled program each
operation belongs to (``jit_<function>(<fingerprint>)``).  The
benchmark brackets every operation it drives with a
``jax.profiler.TraceAnnotation`` named ``chipbench.<op>``; those appear as
host events, on the same clock, and give the intervals that idle shares are
taken over.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # [start, end) in ns

ANNOTATION_PREFIX = "chipbench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)                    # plane -> (name, t0, t1)
    annotations: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)                    # plane -> (name, t0, t1)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def busy(self, plane: str) -> List[Interval]:
        return union([(t0, t1) for _, t0, t1 in self.ops[plane]])


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name not in lines:
                    continue
                lines[line.name].extend(
                    (e.name, float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns))
                    for e in line.events)
            tr.ops[plane.name] = lines[OPS_LINE]
            tr.modules[plane.name] = lines[MODULES_LINE]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        tr.annotations.append(
                            (e.name[len(ANNOTATION_PREFIX):],
                             float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns)))
    tr.annotations.sort(key=lambda a: a[1])
    return tr


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals covering the same time."""
    out: List[Interval] = []
    for t0, t1 in sorted(intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def overlap(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Time of ``merged`` (disjoint, sorted) inside [lo, hi)."""
    if hi <= lo or not merged:
        return 0.0
    i = max(bisect.bisect_right([a for a, _ in merged], lo) - 1, 0)
    tot = 0.0
    while i < len(merged) and merged[i][0] < hi:
        a, b = merged[i]
        tot += max(0.0, min(b, hi) - max(a, lo))
        i += 1
    return tot


def idle_share(merged: Sequence[Interval],
               spans: Sequence[Interval]) -> Optional[float]:
    """1 - busy/time over the given intervals, or None when they hold no
    time."""
    total = sum(b - a for a, b in spans)
    if total <= 0:
        return None
    busy = sum(overlap(merged, a, b) for a, b in spans)
    return 1.0 - busy / total


def instruction(name: str) -> str:
    """The HLO instruction an op event names (``%delta_pack_pallas.2`` of
    ``%delta_pack_pallas.2 = (s32[64,1,128]...) custom-call(...)``)."""
    return name.split(" = ", 1)[0]


def kernel_time_s(tr: Trace, prefixes: Sequence[str]) -> float:
    """Summed device time of the operations whose instruction name starts
    with one of ``prefixes``, averaged over the devices.  A Pallas kernel's
    instruction is named after the jitted function that calls it
    (``%delta_pack_pallas.N``)."""
    if not tr.ops:
        return 0.0
    pre = tuple(prefixes)
    tot = sum(t1 - t0 for evs in tr.ops.values()
              for name, t0, t1 in evs if instruction(name).startswith(pre))
    return tot / len(tr.ops) / 1e9


def module_busy(tr: Trace, plane: str,
                prefixes: Sequence[str]) -> List[Interval]:
    """Union of the intervals of the compiled programs whose name starts
    with one of ``prefixes`` (``jit__pack_words`` for the program of the
    function ``_pack_words``)."""
    pre = tuple(prefixes)
    return union([(t0, t1) for name, t0, t1 in tr.modules.get(plane, [])
                  if name.startswith(pre)])


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The k device operations that took most time: [name, seconds],
    averaged over the devices."""
    tot: Dict[str, float] = {}
    for evs in tr.ops.values():
        for name, t0, t1 in evs:
            tot[name] = tot.get(name, 0.0) + (t1 - t0)
    n = max(len(tr.ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in best]


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi) between the busy ones."""
    out: List[Interval] = []
    t = lo
    for a, b in merged:
        if b <= lo:
            continue
        if a >= hi:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def idle_by_label(merged: Sequence[Interval], lo: float, hi: float,
                  label_all: Callable[[List[Interval]], List[str]],
                  k: int = 10) -> List[List]:
    """Idle time of [lo, hi) summed by what the host was doing in each gap
    (``label_all(gaps)`` gives one label per gap): the k largest,
    [label, seconds]."""
    idle = gaps(merged, lo, hi)
    tot: Dict[str, float] = {}
    for (a, b), name in zip(idle, label_all(idle)):
        tot[name] = tot.get(name, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in best]
