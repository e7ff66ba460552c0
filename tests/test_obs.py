"""Observability plane (DESIGN.md §16): span tracer, metrics registry,
InstrumentedStore over every backend, session pipeline integration,
kernel-fallback scoping, and the export surfaces (Chrome trace JSON,
Prometheus text via CLI and the kishud socket)."""
import json
import re

import numpy as np
import pytest

from repro.core import KishuSession, MemoryStore, open_store
from repro.obs import (SessionObs, TRACE_META_PREFIX, Tracer, active,
                       chrome_trace, render, spans_from_doc)
from repro.obs.instrument import (InstrumentedStore, backend_label,
                                  instrument_tree)
from repro.obs.metrics import Histogram, MetricsRegistry

# every line of a Prometheus text exposition: comment or sample
_EXPO_LINE = re.compile(
    r"^(# (TYPE|HELP) .*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.e]+)$")


def _assert_exposition(text: str) -> None:
    lines = [ln for ln in text.splitlines() if ln]
    assert lines, "empty exposition"
    for ln in lines:
        assert _EXPO_LINE.match(ln), f"bad exposition line: {ln!r}"


def set_val(ns, name, val):
    ns[name] = np.full(256, float(val), np.float32)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_nesting_and_ids():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner", k=1):
            pass
    by_name = {r.name: r for r in tr.spans}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].parent_id is None
    assert by_name["inner"].args == {"k": 1}
    # inner recorded first (exit order), intervals nest
    o, i = by_name["outer"], by_name["inner"]
    assert o.t0_s <= i.t0_s and i.t0_s + i.dur_s <= o.t0_s + o.dur_s + 1e-9


def test_tracer_disabled_is_noop_and_ring_bounds():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        pass
    assert len(tr.spans) == 0
    tr = Tracer(enabled=True, max_spans=8)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans) == 8
    assert [r.name for r in tr.spans][-1] == "s49"


def test_tracer_stage_totals_and_doc_roundtrip():
    tr = Tracer(enabled=True)
    for _ in range(3):
        with tr.span("stage_a"):
            pass
    totals = tr.stage_totals()
    assert set(totals) == {"stage_a"} and totals["stage_a"] >= 0
    back = spans_from_doc(tr.to_doc())
    assert [r.name for r in back] == [r.name for r in tr.spans]
    assert back[0].span_id == list(tr.spans)[0].span_id


def test_chrome_trace_format():
    tr = Tracer(enabled=True)
    with tr.span("commit", command="c1"):
        with tr.span("detect"):
            pass
    doc = chrome_trace(list(tr.spans))
    evs = doc["traceEvents"]
    assert len(evs) == 2 and doc["displayTimeUnit"] == "ms"
    for e in evs:
        assert e["ph"] == "X" and e["dur"] > 0 and "ts" in e
        assert "span_id" in e["args"]
    # sorted by ts: parent (earlier start) first
    assert evs[0]["name"] == "commit" and evs[1]["name"] == "detect"
    assert evs[1]["args"]["parent_id"] == evs[0]["args"]["span_id"]
    assert evs[0]["args"]["command"] == "c1"
    json.dumps(doc)     # JSON-serializable end to end


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_histogram_buckets_and_exposition():
    reg = MetricsRegistry()
    h = reg.histogram("kishu_test_seconds", base=1e-6)
    for v in (1e-6, 3e-6, 1e-3, 0.5):
        h.observe(v)
    assert h.count == 4 and abs(h.sum - 0.501004) < 1e-6
    c = reg.counter("kishu_test_total", op="get")
    c.inc(3)
    text = render([reg])
    _assert_exposition(text)
    assert 'kishu_test_total{op="get"} 3' in text
    assert "kishu_test_seconds_count 4" in text
    # cumulative le= buckets are monotone non-decreasing
    counts = [float(m.group(1)) for m in re.finditer(
        r'kishu_test_seconds_bucket\{le="[^"]*"\} ([0-9.]+)', text)]
    assert counts == sorted(counts) and counts[-1] == 4


def test_registry_doc_roundtrip_and_const_labels():
    reg = MetricsRegistry(const_labels={"tenant": "t1"})
    reg.counter("kishu_x_total").inc()
    reg.histogram("kishu_y_seconds").observe(0.01)
    back = MetricsRegistry.from_doc(reg.to_doc())
    text = render([back])
    _assert_exposition(text)
    assert 'tenant="t1"' in text
    assert 'kishu_y_seconds_count{tenant="t1"} 1' in text
    assert back.counter_total("kishu_x_total") == 1


# ---------------------------------------------------------------------------
# InstrumentedStore — every base backend + a fabric composition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("uri", ["memory://", "dir://{tmp}/cas",
                                 "sqlite://{tmp}/cas.db"])
def test_instrumented_store_backends(uri, tmp_path):
    store = open_store(uri.format(tmp=tmp_path))
    label = backend_label(store)
    reg = MetricsRegistry()
    inst = InstrumentedStore(store, reg)
    inst.put_chunks([("k1", b"x" * 64), ("k2", b"y" * 64)])
    got = inst.get_chunks(["k1", "k2"])
    assert got["k1"] == b"x" * 64
    inst.put_meta("m/doc", {"a": 1})
    assert inst.get_meta("m/doc") == {"a": 1}
    text = render([reg])
    _assert_exposition(text)
    for op in ("put_chunks", "get_chunks", "put_meta", "get_meta"):
        assert (f'kishu_store_op_seconds_count'
                f'{{backend="{label}",op="{op}"}} 1') in text
    assert (f'kishu_store_bytes_total'
            f'{{backend="{label}",dir="put"}} 128') in text
    assert (f'kishu_store_bytes_total'
            f'{{backend="{label}",dir="get"}} 128') in text


def test_instrument_tree_fabric_composition(tmp_path):
    uri = (f"fabric://shard(rep(dir://{tmp_path}/a0,dir://{tmp_path}/a1),"
           f"sqlite://{tmp_path}/b.db)")
    store = open_store(uri)
    reg = MetricsRegistry()
    inst = instrument_tree(store, reg)
    inst.put_chunks([(f"k{i}", bytes([i]) * 32) for i in range(16)])
    inst.get_chunks([f"k{i}" for i in range(16)])
    text = render([reg])
    _assert_exposition(text)
    # root labeled as the shard router, children per slot:backend
    assert 'backend="shard"' in text
    assert 'backend="shard0:rep"' in text
    assert 'backend="shard1:sqlite"' in text
    # both shards actually saw traffic
    for b in ("shard0:rep", "shard1:sqlite"):
        n = re.search(r'kishu_store_op_seconds_count'
                      r'\{backend="%s",op="put_chunks"\} (\d+)' % b, text)
        assert n and int(n.group(1)) >= 1


def test_instrumented_store_passthrough_semantics():
    inner = MemoryStore()
    reg = MetricsRegistry()
    inst = InstrumentedStore(inner, reg)
    docs = {"a/1": {"v": 1}, "a/2": {"v": 2}}
    inst.put_meta_batch(docs)            # dict-shaped batch API preserved
    assert inner.get_meta("a/2") == {"v": 2}
    assert sorted(inst.list_meta("a/")) == ["a/1", "a/2"]
    inst.put_chunks([("k", b"z")])
    assert inst.delete_chunks(["k"]) == 1   # int return forwarded


# ---------------------------------------------------------------------------
# session pipeline integration
# ---------------------------------------------------------------------------

def _traced_session(store, **kw):
    sess = KishuSession(store, chunk_bytes=1 << 10, trace=True, **kw)
    sess.register("set_val", set_val)
    sess.init_state({})
    return sess


def test_session_trace_covers_pipelines_and_nests(tmp_path):
    sess = _traced_session(open_store(f"sqlite://{tmp_path}/cas.db"))
    c1 = sess.run("set_val", name="x", val=1)
    sess.run("set_val", name="x", val=2)
    sess.checkout(c1)
    spans = list(sess.obs.tracer.spans)
    names = {r.name for r in spans}
    assert {"commit", "detect", "serialize", "put_chunks", "publish",
            "checkout", "plan"} <= names
    assert len(names) >= 6
    by_id = {r.span_id: r for r in spans}
    nested = 0
    for r in spans:
        if r.parent_id is None:
            continue
        p = by_id[r.parent_id]
        assert p.t0_s - 1e-6 <= r.t0_s
        assert r.t0_s + r.dur_s <= p.t0_s + p.dur_s + 1e-6
        nested += 1
    assert nested > 0
    # store-op histograms populated for the sqlite backend
    text = sess.metrics_text()
    _assert_exposition(text)
    assert 'backend="sqlite"' in text and "kishu_store_op_seconds" in text
    sid = sess.obs.sid
    sess.close()
    # trace persisted on close, loadable via the meta plane
    store = open_store(f"sqlite://{tmp_path}/cas.db")
    doc = store.get_meta(TRACE_META_PREFIX + sid)
    assert doc and [r.name for r in spans_from_doc(doc["spans"])]


def test_untraced_session_records_nothing_but_metrics(tmp_path):
    sess = KishuSession(open_store(f"dir://{tmp_path}/cas"),
                        chunk_bytes=1 << 10)
    sess.register("set_val", set_val)
    sess.init_state({})
    sess.run("set_val", name="x", val=1)
    assert len(sess.obs.tracer.spans) == 0
    assert "kishu_store_op_seconds" in sess.metrics_text()
    sid = sess.obs.sid
    sess.close()
    # no trace doc written when tracing was off
    assert open_store(f"dir://{tmp_path}/cas").get_meta(
        TRACE_META_PREFIX + sid) is None


def test_trace_env_var_opt_in(tmp_path, monkeypatch):
    monkeypatch.setenv("KISHU_TRACE", "1")
    sess = KishuSession(MemoryStore(), chunk_bytes=1 << 10)
    assert sess.obs.tracer.enabled
    sess.close()


# ---------------------------------------------------------------------------
# kernel-fallback scoping (satellite: per-session registry + module shim)
# ---------------------------------------------------------------------------

def test_kernel_fallback_scoped_per_session():
    from repro.core import delta as delta_mod
    a, b = SessionObs(), SessionObs()
    err = RuntimeError("no kernel")
    with a.activate():
        assert active() is a
        delta_mod.note_kernel_fallback("t1", err)
        delta_mod.note_kernel_fallback("t1", err)
        assert delta_mod.kernel_fallbacks() == 2
    with b.activate():
        assert delta_mod.kernel_fallbacks() == 0     # b's counter, not a's
        delta_mod.note_kernel_fallback("t1", err)
        assert delta_mod.kernel_fallbacks() == 1
    assert active() is None
    assert a.kernel_fallbacks() == 2 and b.kernel_fallbacks() == 1


def test_kernel_fallback_outside_a_session_logs_and_counts_nowhere(caplog):
    import logging

    from repro.core import delta as delta_mod
    a = SessionObs()
    with caplog.at_level(logging.WARNING, logger="repro.core.delta"):
        delta_mod.note_kernel_fallback("loose", RuntimeError("no kernel"))
    logged = [r for r in caplog.records if "device kernel" in r.message]
    assert len(logged) == 1 and "loose" in logged[0].getMessage()
    assert delta_mod.kernel_fallbacks() == 0
    with a.activate():
        delta_mod.note_kernel_fallback("inside", RuntimeError("no kernel"))
        assert delta_mod.kernel_fallbacks() == 1
    assert a.kernel_fallbacks() == 1
    assert SessionObs().kernel_fallbacks() == 0


# ---------------------------------------------------------------------------
# export surfaces: CLI + kishud socket
# ---------------------------------------------------------------------------

@pytest.fixture
def traced_store_uri(tmp_path):
    uri = f"dir://{tmp_path}/cas"
    sess = _traced_session(open_store(uri))
    c1 = sess.run("set_val", name="x", val=1)
    sess.run("set_val", name="y", val=2)
    sess.checkout(c1)
    sess.close()
    return uri


def test_cli_trace_exports_chrome_json(traced_store_uri, tmp_path, capsys):
    from repro.launch.kishu_cli import main as cli
    out_path = tmp_path / "trace.json"
    assert cli(["--store", traced_store_uri, "trace",
                "--out", str(out_path)]) == 0
    doc = json.loads(out_path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) >= 6
    assert all(e["ph"] == "X" and "ts" in e and "dur" in e for e in evs)
    assert len({e["name"] for e in evs}) >= 6
    # stdout mode too
    assert cli(["--store", traced_store_uri, "trace"]) == 0
    doc2 = json.loads(capsys.readouterr().out)
    assert len(doc2["traceEvents"]) == len(evs)


def test_cli_stats_metrics_exposition(traced_store_uri, capsys):
    from repro.launch.kishu_cli import main as cli
    assert cli(["--store", traced_store_uri, "stats", "--metrics"]) == 0
    text = capsys.readouterr().out
    _assert_exposition(text)
    assert "kishu_graph_commits" in text
    assert "kishu_store_op_seconds" in text
    # persisted per-session snapshots merged in, tagged by sid
    assert 'sid="' in text
    # plain stats unaffected
    assert cli(["--store", traced_store_uri, "stats"]) == 0
    assert "chunks" in capsys.readouterr().out


def test_kishud_metrics_socket_roundtrip(tmp_path):
    from repro.launch.kishud import Kishud, KishudServer, control
    d = Kishud(MemoryStore(), workers=1, lease_ttl_s=30.0,
               chunk_bytes=1 << 9)
    sock = str(tmp_path / "kd.sock")
    srv = KishudServer(d, sock)
    try:
        s = d.session("alice")
        s.register("set_val", set_val)
        s.init_state({})
        s.run("set_val", name="x", val=1)
        resp = control(sock, "metrics")
        assert resp["ok"]
        text = resp["metrics"]
        _assert_exposition(text)
        assert "kishud_uptime_seconds" in text
        assert "kishud_sessions 1" in text
        assert 'tenant="alice"' in text
        assert "kishu_store_op_seconds" in text
    finally:
        srv.close()
        d.close()
