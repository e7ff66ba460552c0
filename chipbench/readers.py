"""What a per-layer metric's reader sees of a run.

A reader is ``metrics/<name>.py`` with ``read(ctx) -> float | None``: it
takes its number from the session's spans (``ctx.spans``), from the
profiler trace (``ctx.trace``), or from the window's own records, and
returns None when the run gives it nothing to read.

Spans are the session's obs spans of the window (the tracer is cleared when
the window starts): dicts with ``id``, ``parent``, ``name``, ``t0`` (seconds
since the tracer's epoch, on ``time.monotonic``) and ``dur``.  Trace times
are nanoseconds of the profiler's clock; ``to_trace_ns`` maps a monotonic
time onto it through the annotations that bracket each operation.
"""
from __future__ import annotations

import heapq
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import peaks, stats
from chipbench import trace as trace_mod


@dataclass
class Context:
    run: object                                  # bench.Run
    trace: trace_mod.Trace = field(default_factory=trace_mod.Trace)
    peak: Optional[peaks.Peak] = None

    def __post_init__(self):
        r = self.run
        if self.peak is None:
            self.peak = peaks.peak_for(r.devices[0].device_kind
                                       if r.devices else "")
        self.spans: List[dict] = r.sess.spans() if self.trace_on else []
        self.epoch = r.sess.span_epoch() if self.trace_on else 0.0
        self.by_id = {s["id"]: s for s in self.spans}
        self.kids: Dict[int, List[dict]] = {}
        for s in self.spans:
            self.kids.setdefault(s["parent"], []).append(s)
        self.chunk_bytes = r.chunk_bytes
        self.commits = r.window.of("commit")
        self.checkouts = r.window.of("checkout")
        self._offset = self._clock_offset()

    @property
    def trace_on(self) -> bool:
        return bool(getattr(self.run, "trace", False))

    # ---- window records -----------------------------------------------------
    @property
    def n_commits(self) -> int:
        return len(self.commits)

    @property
    def n_checkouts(self) -> int:
        return len(self.checkouts)

    def commit_s(self) -> Optional[float]:
        return stats.per_op(sum(o.seconds for o in self.commits)
                            + self.run.window.flush_s, len(self.commits))

    def checkout_s(self) -> Optional[float]:
        return stats.per_op(sum(o.seconds for o in self.checkouts),
                            len(self.checkouts))

    def cell_dirty_bytes(self, commit: str) -> int:
        return self.run.cell_dirty_bytes(commit)

    def cell_flops(self, commit: str) -> float:
        return self.run.cell_flops(commit)

    def checkout_bytes(self, op) -> int:
        return self.run.checkout_bytes(op)

    # ---- spans --------------------------------------------------------------
    def roots(self, name: str) -> List[dict]:
        return [s for s in self.spans
                if s["name"] == name and s["parent"] is None]

    def children(self, span: dict, names: Sequence[str] = ()) -> List[dict]:
        return [s for s in self.kids.get(span["id"], [])
                if not names or s["name"] in names]

    def named(self, name: str) -> List[dict]:
        return [s for s in self.spans if s["name"] == name]

    def chain(self, span: dict) -> str:
        names = []
        s: Optional[dict] = span
        while s is not None:
            names.append(s["name"])
            s = self.by_id.get(s["parent"])
        return "/".join(reversed(names))

    # ---- the trace ----------------------------------------------------------
    def _clock_offset(self) -> float:
        """Trace ns minus monotonic ns, from the operations' annotations."""
        anns = [a for a in self.trace.annotations
                if a[0] in ("commit", "checkout")]
        ops = sorted(self.run.window.ops, key=lambda o: o.t0)
        if not anns or len(anns) != len(ops):
            return 0.0
        return statistics.median(a[1] - o.t0 * 1e9
                                 for a, o in zip(anns, ops))

    def to_trace_ns(self, mono_s: float) -> float:
        return mono_s * 1e9 + self._offset

    def annotated(self, name: str) -> List[Tuple[float, float]]:
        return [(a, b) for n, a, b in self.trace.annotations if n == name]

    def busy(self) -> List[Tuple[float, float]]:
        """Union of the device operations' intervals (first device)."""
        if not self.trace.devices:
            return []
        return self.trace.busy(self.trace.devices[0])

    def window_interval(self) -> Optional[Tuple[float, float]]:
        w = self.annotated("window")
        return w[0] if w else None

    def device_summary(self) -> Dict[str, float]:
        win = self.window_interval()
        if win is None or not self.trace.devices:
            return {"busy_s": 0.0, "window_s": 0.0}
        busy = 0.0
        for dev in self.trace.devices:
            busy += trace_mod.overlap(self.trace.busy(dev), *win)
        busy /= len(self.trace.devices)
        return {"busy_s": busy / 1e9, "window_s": (win[1] - win[0]) / 1e9}

    def _labels(self, idle: List[Tuple[float, float]]) -> List[str]:
        """For each gap, the deepest span the host was in at its middle
        (the open span that started last), else the annotation."""
        spans = sorted(
            ((self.to_trace_ns(self.epoch + s["t0"]), s) for s in self.spans),
            key=lambda t: t[0])
        order = sorted(range(len(idle)), key=lambda i: sum(idle[i]))
        out = [""] * len(idle)
        active: Dict[int, Tuple[float, dict]] = {}
        ends: List[Tuple[float, int]] = []
        j = 0
        for i in order:
            mid = (idle[i][0] + idle[i][1]) / 2
            while j < len(spans) and spans[j][0] <= mid:
                a, s = spans[j]
                active[s["id"]] = (a, s)
                heapq.heappush(ends, (a + s["dur"] * 1e9, s["id"]))
                j += 1
            while ends and ends[0][0] <= mid:
                active.pop(heapq.heappop(ends)[1], None)
            if active:
                out[i] = self.chain(max(active.values(),
                                        key=lambda t: t[0])[1])
                continue
            out[i] = next((n for n, a, b in self.trace.annotations
                           if n != "window" and a <= mid < b),
                          "between operations")
        return out

    def breakdown(self) -> Dict[str, List[List]]:
        win = self.window_interval()
        out = {"device_ops": trace_mod.top_ops(self.trace),
               "idle_gaps": []}
        if win is not None and self.trace.devices:
            out["idle_gaps"] = trace_mod.idle_by_label(
                self.busy(), win[0], win[1], self._labels)
        return out

    def kernel_time_s(self, prefixes: Sequence[str]) -> float:
        return trace_mod.kernel_time_s(self.trace, prefixes)
