"""The readers of the program's own spans: ``program_trace`` finds the
``kishu.*`` events a traced session writes into a profiler trace and joins
them to the spans by id, and each reader gives the right number on a run
made up here, and None where the program wrote no such span."""
import glob
import os

import pytest

import chipbench_testkit  # noqa: F401  (puts the checkout on sys.path)
from chipbench import bench, program_trace


def _span(sid, name, t0, dur, parent=None, **args):
    return {"id": sid, "parent": parent, "name": name, "t0": t0, "dur": dur,
            "args": args}


class _Ctx:
    """What a reader sees of a run: spans, the trace's annotations and
    device busy time, and the program's events (ns)."""

    def __init__(self, spans, *, commits=2, checkouts=2, events=(),
                 annotations=(), busy=(), devices=("/device:TPU:0",)):
        self.spans = spans
        self.n_commits = commits
        self.n_checkouts = checkouts
        self.program_events = list(events)
        self._annotations = list(annotations)
        self._busy = list(busy)
        self.trace = type("T", (), {"devices": list(devices)})()

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def roots(self, name):
        return [s for s in self.named(name) if s["parent"] is None]

    def annotated(self, name):
        return [(a, b) for n, a, b in self._annotations if n == name]

    def busy(self):
        return self._busy


def _read(metric, ctx):
    return bench.load_reader(metric)(ctx)


SPANS = [
    _span(1, "commit", 0.0, 1.0),
    _span(2, "pack_sync", 0.1, 0.010, 1, bytes=1000),
    _span(3, "pack_sync", 0.2, 0.030, 1, bytes=3000),
    _span(4, "d2h", 0.3, 0.050, 1, bytes=2_000_000),
    _span(5, "manifest", 0.4, 0.2, 1, key="a", keys_s=0.12, has_s=0.02,
          chunks=9),
    _span(6, "manifest", 0.6, 0.1, 1, key="b"),        # not timed
    _span(7, "checkout", 2.0, 0.5),
    _span(8, "read", 2.1, 0.040, 7, bytes=500),
    _span(9, "h2d", 2.2, 0.060, 7, bytes=4_000_000),
    _span(10, "checkout", 3.0, 0.2),
]


def test_span_readers():
    ctx = _Ctx(SPANS)
    assert _read("pack_sync_ms.sparse", ctx) == pytest.approx(20.0)
    assert _read("writer_d2h_ms.rollback", ctx) == pytest.approx(25.0)
    assert _read("writer_keys_ms.rollback", ctx) == pytest.approx(70.0)
    assert _read("load_read_ms.rollback", ctx) == pytest.approx(20.0)
    assert _read("load_h2d_ms.rollback", ctx) == pytest.approx(30.0)
    assert _read("d2h_MB_per_commit.sparse", ctx) == pytest.approx(1.002)
    assert _read("h2d_MB_per_checkout.rollback", ctx) == pytest.approx(2.0)


def test_trace_readers():
    ms = 1e6
    events = [program_trace.Event("pack_sync", 2, 100 * ms, 110 * ms),
              program_trace.Event("pack_sync", 3, 200 * ms, 230 * ms),
              program_trace.Event("checkout", 7, 2000 * ms, 2500 * ms),
              program_trace.Event("checkout", 10, 3000 * ms, 3200 * ms)]
    annotations = [("checkout", 1999 * ms, 2900 * ms),
                   ("checkout", 2999 * ms, 3300 * ms)]
    # the device runs 4 ms inside the first sync and 30 ms in the second
    busy = [(104 * ms, 108 * ms), (190 * ms, 240 * ms)]
    ctx = _Ctx(SPANS, events=events, annotations=annotations, busy=busy)
    assert _read("pack_sync_idle_ms.sparse", ctx) == pytest.approx(3.0)
    assert _read("checkout_tail_ms.sparse", ctx) == pytest.approx(250.0)
    # no device plane: nothing idle to read
    ctx = _Ctx(SPANS, events=events, annotations=annotations, busy=busy,
               devices=())
    assert _read("pack_sync_idle_ms.sparse", ctx) is None


NEW = ["pack_sync_ms.sparse", "pack_sync_idle_ms.sparse",
       "checkout_tail_ms.sparse", "writer_d2h_ms.rollback",
       "writer_keys_ms.rollback", "load_read_ms.rollback",
       "load_h2d_ms.rollback", "d2h_MB_per_commit.rollback",
       "h2d_MB_per_checkout.sparse"]


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_spans_reads_nothing(metric):
    """The parent program wrote neither these spans nor ``kishu.*``
    events: its spans (the older names) and trace give None, not 0."""
    older = [_span(1, "commit", 0.0, 1.0), _span(2, "detect", 0.1, 0.5, 1),
             _span(3, "delta_pack", 0.2, 0.1, 2, nbytes=64),
             _span(4, "checkout", 2.0, 0.5), _span(5, "fetch", 2.1, 0.1, 4)]
    ms = 1e6
    ctx = _Ctx(older, annotations=[("checkout", 1999 * ms, 2900 * ms)],
               busy=[(0, 10 * ms)])
    assert _read(metric, ctx) is None


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_events_of_a_recorded_session_join_its_spans(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core import KishuSession, MemoryStore

    monkeypatch.setenv("KISHU_DEVICE_DELTA", "1")
    sess = KishuSession(MemoryStore(), chunk_bytes=1 << 12, cache_bytes=0,
                        trace=True)
    sess.register("init", lambda ns: ns.__setitem__(
        "x", jnp.arange(1 << 14, dtype=jnp.float32)))
    sess.register("edit", lambda ns: ns.__setitem__(
        "x", ns["x"].at[7].set(-1.0)))
    sess.init_state({})
    base = sess.run("init")
    sess.obs.tracer.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.run("edit")
        sess.checkout(base)
    finally:
        jax.profiler.stop_trace()
    spans = sess.obs.tracer.to_doc()
    sess.close()

    run = type("R", (), {"trace_dir": str(tmp_path)})()
    ctx = type("C", (), {"run": run})()
    evs = program_trace.events(ctx)
    assert ctx.program_events is evs               # read once per run
    assert sorted(e.span_id for e in evs) == sorted(s["id"] for s in spans)
    pairs = program_trace.joined(ctx, spans)
    assert len(pairs) == len(spans)
    for s, ev in pairs:
        assert ev.name == s["name"] and ev.t0 <= ev.t1
    (sync, ev), = [p for p in pairs if p[0]["name"] == "pack_sync"]
    assert ev.args["bytes"] == sync["args"]["bytes"] == 16 * 12 + 4
    # a directory with no trace in it gives no events
    empty = type("C", (), {"run": type("R", (), {"trace_dir": str(
        tmp_path / "none")})()})()
    assert program_trace.events(empty) == []
    assert glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))
