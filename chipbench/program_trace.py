"""The program's own spans in the profiler trace.

While a session traces, every obs span also enters a
``jax.profiler.TraceAnnotation`` named ``kishu.<name>`` with the span's
``span_id`` and args as metadata (``repro.obs.trace``), so the run's
``.xplane.pb`` holds each span as a host event on the clock of the device
trace, with no offset fitted between two clocks.  ``events`` reads those
events once per run; ``joined`` pairs them with the session's spans
(``ctx.spans``) by ``span_id``.  A program that writes no such events gives
empty lists, and the readers built on them return None.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace as trace_mod

PREFIX = "kishu."


@dataclass
class Event:
    name: str                    # the span's name, less the prefix
    span_id: Optional[int]
    t0: float                    # ns on the trace's clock
    t1: float
    args: Dict[str, object] = field(default_factory=dict)


def load(path: str) -> List[Event]:
    """The ``kishu.*`` events of every host plane of a trace file, in order
    of their start."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                with warnings.catch_warnings():
                    # jaxlib's stats type warns as it is iterated
                    warnings.simplefilter("ignore", DeprecationWarning)
                    args = dict(e.stats)
                sid = args.pop("span_id", None)
                t0 = float(e.start_ns)
                out.append(Event(e.name[len(PREFIX):],
                                 None if sid is None else int(sid), t0,
                                 t0 + float(e.duration_ns), args))
    out.sort(key=lambda ev: ev.t0)
    return out


def events(ctx) -> List[Event]:
    """The run's ``kishu.*`` events, read from its trace directory on the
    first call and kept on ``ctx``."""
    got = getattr(ctx, "program_events", None)
    if got is None:
        trace_dir = getattr(getattr(ctx, "run", None), "trace_dir", None)
        path = trace_mod.find_xplane(trace_dir) if trace_dir else None
        got = load(path) if path else []
        ctx.program_events = got
    return got


def joined(ctx, spans: Sequence[dict]) -> List[Tuple[dict, Event]]:
    """Each of ``spans`` with its event in the trace, where it has one."""
    by_id = {ev.span_id: ev for ev in events(ctx)}
    return [(s, by_id[s["id"]]) for s in spans if s["id"] in by_id]
