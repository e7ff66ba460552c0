"""The obs plane on the profiler's clock, and the copy counters: every span
of a traced session is also a ``kishu.<name>`` event of a ``jax.profiler``
trace, nested as the spans are; the disabled tracer builds nothing; the
device→host and host→device counters equal the bytes each path moves; a
full span ring counts what it drops; off-thread spans name the operation
that queued their work."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import KishuSession, MemoryStore
from repro.obs import trace as trace_mod

CHUNK = 4096                      # 16 chunks of the 64 KiB test array
N = 16 * CHUNK // 4


def _kishu_events(log_dir):
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(trace_mod.ANNOTATION_PREFIX):
                    out.append((e.name, dict(e.stats), e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def _session(monkeypatch, *, trace=True, delta="1", codec="0",
             scatter="1", **kw):
    monkeypatch.setenv("KISHU_DEVICE_DELTA", delta)
    monkeypatch.setenv("KISHU_DEVICE_HASH", "1")
    monkeypatch.setenv("KISHU_DEVICE_CODEC", codec)
    monkeypatch.setenv("KISHU_DEVICE_SCATTER", scatter)
    sess = KishuSession(MemoryStore(), chunk_bytes=CHUNK, cache_bytes=0,
                        trace=trace, **kw)

    def init(ns):
        ns["x"] = jnp.arange(N, dtype=jnp.float32)

    def touch(ns, chunks):
        idx = jnp.asarray([c * CHUNK // 4 for c in chunks])
        ns["x"] = ns["x"].at[idx].set(-1.0)

    def bump(ns):
        ns["x"] = ns["x"] + 1.0

    for name, fn in (("init", init), ("touch", touch), ("bump", bump)):
        sess.register(name, fn)
    sess.init_state({})
    return sess


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_traced_commit_and_checkout_land_in_the_profiler_trace(
        monkeypatch, tmp_path):
    sess = _session(monkeypatch)
    base = sess.run("init")
    sess.obs.tracer.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.run("touch", chunks=[0, 5, 9])
        sess.checkout(base)
    finally:
        jax.profiler.stop_trace()
    spans = sess.obs.tracer.to_doc()
    sess.close()
    events = _kishu_events(str(tmp_path))
    by_id = {st["span_id"]: (name, st, a, b) for name, st, a, b in events}
    # one event per span, under the span's own name, with its args
    assert len(events) == len(spans) == len(by_id)
    for s in spans:
        name, st, _, _ = by_id[s["id"]]
        assert name == "kishu." + s["name"]
        for k, v in s["args"].items():
            assert st[k] == (v if isinstance(v, (int, float, str))
                             else str(v))
    names = {s["name"] for s in spans}
    assert {"commit", "detect", "pack_sync", "manifest", "d2h", "checkout",
            "patch", "scatter_dev", "h2d"} <= names
    # nested as the spans are
    for s in spans:
        if s["parent"] is None:
            continue
        _, _, a, b = by_id[s["id"]]
        _, _, pa, pb = by_id[s["parent"]]
        assert pa <= a and b <= pb
    # the spans' own durations on the profiler's clock
    for s in spans:
        _, _, a, b = by_id[s["id"]]
        assert (b - a) / 1e9 <= s["dur"] + 1e-3


def test_the_disabled_tracer_builds_no_annotation(monkeypatch):
    built = []

    class Fake:
        enabled = True

        @classmethod
        def is_enabled(cls):
            return cls.enabled

        def __init__(self, name, **meta):
            built.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **meta):
            built[-1][1].update(meta)

    monkeypatch.setattr(trace_mod, "_annotation", lambda: Fake)
    off = trace_mod.Tracer(enabled=False)
    assert off.span("commit", command="c") is obs.NULL_SPAN
    with off.span("commit") as sp:
        sp.set(bytes=1)
    assert built == []
    on = trace_mod.Tracer(enabled=True)
    Fake.enabled = False              # no profiler session running
    with on.span("detect"):
        pass
    assert built == []
    Fake.enabled = True
    with on.span("d2h", bytes=8) as sp:
        sp.set(more=2)
    (name, meta), = built
    assert name == "kishu.d2h"
    assert meta == {"span_id": sp.span_id, "bytes": 8, "more": 2}
    assert [r.args for r in on.spans][-1] == {"bytes": 8, "more": 2}
    # outside any session the helpers are no-ops
    assert obs.span("x") is obs.NULL_SPAN
    obs.count("kishu_d2h_bytes_total", 5, path="pack")


def _labelled(sess, name, label):
    out = {}
    for (n, labels), c in sess.obs.registry._counters.items():
        if n == name:
            out[dict(labels)[label]] = c.value
    return out


def test_byte_counters_equal_the_bytes_each_path_moves(monkeypatch):
    sess = _session(monkeypatch, trace=False)
    base = sess.run("init")                 # first version: a whole leaf
    assert _labelled(sess, "kishu_d2h_bytes_total", "path") == {
        "leaf": N * 4}
    sess.run("touch", chunks=[0, 5, 9])     # pack: syncs + 3 rows
    d2h = _labelled(sess, "kishu_d2h_bytes_total", "path")
    assert d2h["pack"] == 16 * 12 + 4 + 3 * CHUNK
    assert _labelled(sess, "kishu_host_syncs_total", "where") == {
        "leaf": 1, "delta_pack": 3, "read_chunks": 1}
    sess.checkout(base)                     # scatter: 4 pow2 rows + idx
    assert _labelled(sess, "kishu_h2d_bytes_total", "path") == {
        "scatter": 4 * CHUNK + 4 * 4}
    sess.run("bump")                        # every chunk dirty: the leaf
    before = sess.obs.d2h_bytes()
    tip = sess.run("bump")
    assert sess.obs.d2h_bytes() - before == 16 * 12 + 4 + N * 4
    sess.checkout(base)                     # fully diverged: a full load
    sess.checkout(tip)
    assert _labelled(sess, "kishu_h2d_bytes_total", "path")["load"] == \
        2 * N * 4
    sess.close()


@pytest.mark.parametrize("path", ["leaf", "pack", "codec", "range"])
def test_write_stats_dev2host_equals_the_counter(monkeypatch, path):
    sess = _session(monkeypatch, trace=False,
                    delta="0" if path == "range" else "1",
                    codec="1" if path == "codec" else "0")
    sess.run("init")
    before = _labelled(sess, "kishu_d2h_bytes_total", "path")
    if path == "leaf":
        sess.run("bump")
    else:
        sess.run("touch", chunks=[0, 5, 9])
    w = sess.last_run.write
    after = _labelled(sess, "kishu_d2h_bytes_total", "path")
    moved = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    assert w.bytes_dev2host == sum(moved.values()) > 0
    assert moved[path] > 0
    assert sess.graph.nodes[sess.head].stats["bytes_dev2host"] == \
        w.bytes_dev2host
    if path == "codec":
        assert w.chunks_encoded > 0
    sess.close()


def test_spans_dropped_counts_ring_evictions():
    o = obs.SessionObs(trace=True, max_spans=4)
    with o.activate():
        for i in range(10):
            with obs.span(f"s{i}"):
                pass
    assert len(o.tracer.spans) == 4
    assert o.registry.counter_total("kishu_spans_dropped_total") == 6
    assert "kishu_spans_dropped_total" in obs.render([o.registry])


def test_off_thread_spans_carry_the_op_that_queued_them(monkeypatch):
    sess = _session(monkeypatch, async_write=True, async_publish=True)
    sess.run("init")
    sess.writer.flush()
    sess.engine.flush()
    sess.obs.tracer.clear()
    sess.run("touch", chunks=[1, 2])
    sess.writer.flush()
    sess.engine.flush()
    spans = sess.obs.tracer.to_doc()
    sess.close()
    (commit,) = [s for s in spans if s["name"] == "commit"]
    drained = [s for s in spans if s["name"] == "put_chunks"]
    published = [s for s in spans if s["name"] == "publish"
                 and s["parent"] is None and "op" in s["args"]]
    assert drained and all(s["parent"] is None for s in drained)
    assert {s["args"]["op"] for s in drained} == {commit["id"]}
    assert [s["args"]["op"] for s in published] == [commit["id"]]
    # on the commit's own thread nothing carries an op
    assert "op" not in commit["args"]
    assert np.asarray(sess.ns["x"])[CHUNK // 4] == -1.0
