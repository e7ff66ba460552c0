"""Multi-session safety layer: writer leases, tenant namespaces,
refcounted cross-session GC, byte quotas, the kishud daemon and its CLI
verbs (DESIGN.md §14).

The crash-interleaving sweeps live in test_txn_crash.py; this suite pins
the unit-level contracts each of those sweeps relies on.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import txn
from repro.core.chunkstore import (MemoryStore, NamespacedStore, open_store,
                                   tenant_ids, validate_tenant_id)
from repro.core.graph import REFS_DOC, ChunkRefCounts
from repro.core.lease import (Lease, LeaseHeld, LeaseLost, lease_status)
from repro.core.session import KishuSession, QuotaExceededError
from repro.launch.kishu_cli import main as cli
from repro.launch.kishud import (BACKGROUND, INTERACTIVE, AdmissionQueue,
                                 Kishud, KishudServer, control)


def set_val(ns, name, val):
    ns[name] = np.full(400, float(val), np.float32)


def build_session(store, **kw):
    s = KishuSession(store, chunk_bytes=1 << 9, **kw)
    s.register("set_val", set_val)
    return s


# ---------------------------------------------------------------------------
# leases
# ---------------------------------------------------------------------------

def test_lease_acquire_release_cycle():
    store = MemoryStore()
    a = Lease(store, ttl_s=5.0).acquire()
    assert a.held and a.token == 1
    assert lease_status(store)[0]["owner"] == a.owner
    a.release()
    assert not a.held and store.get_meta("lease/writer") is None
    # a clean release removes the doc, so the next grant starts a fresh
    # token chain — fencing only needs monotonicity while a doc exists
    b = Lease(store, ttl_s=5.0).acquire()
    assert b.held and b.token == 1


def test_lease_contender_refused_then_steals_after_observed_ttl():
    store = MemoryStore()
    ttl = 0.2
    a = Lease(store, ttl_s=ttl).acquire()
    contender = Lease(store, ttl_s=ttl)
    with pytest.raises(LeaseHeld):
        contender.acquire(wait_s=0.0)      # holder alive: refused at once
    t0 = time.monotonic()
    contender.acquire(wait_s=ttl * 20, poll_s=0.01)
    waited = time.monotonic() - t0
    assert waited >= ttl, f"stole after only {waited:.3f}s"
    assert contender.token == a.token + 1  # fenced takeover


def test_lease_doc_age_is_never_trusted():
    """A lease doc with an ancient wall-clock ``ts`` (the holder's clock
    stepped, or it simply uses another timezone) must still cost a full
    observed TTL — expiry is observation-based, never doc-declared."""
    store = MemoryStore()
    store.put_meta("lease/writer", {"owner": "ghost", "token": 3,
                                    "ttl_s": 0.2, "ts": 0.0})
    with pytest.raises(LeaseHeld):
        Lease(store, ttl_s=0.2).acquire(wait_s=0.0)
    t0 = time.monotonic()
    Lease(store, ttl_s=0.2).acquire(wait_s=5.0, poll_s=0.01)
    assert time.monotonic() - t0 >= 0.2


def test_lease_renew_detects_takeover_and_release_spares_thief():
    store = MemoryStore()
    a = Lease(store, ttl_s=5.0).acquire()
    thief = Lease(store, ttl_s=5.0).acquire(steal=True)  # operator override
    with pytest.raises(LeaseLost):
        a.renew()
    a.release()                  # deposed: must NOT delete the thief's doc
    doc = store.get_meta("lease/writer")
    assert doc["owner"] == thief.owner and doc["token"] == thief.token


def test_lease_local_expiry_refuses_publish():
    """ensure() past the local horizon raises — the holder would rather
    stop than publish a commit a contender may already have overwritten."""
    store = MemoryStore()
    a = Lease(store, ttl_s=0.05).acquire()
    time.sleep(0.1)
    with pytest.raises(LeaseLost):
        a.ensure()
    assert not a.held


def test_session_publish_fenced_after_steal():
    """End to end: a session whose lease is stolen must refuse its next
    commit (TxnError from the publish guard), leaving the thief's graph
    untouched and the store fsck-clean."""
    from repro.core.txn import TxnError

    store = MemoryStore()
    s = build_session(store, tenant="nb", lease_ttl_s=0.15)
    s.init_state({"a": np.arange(64, dtype=np.float32)})
    good = s.head
    # operator steals the lease out from under the live session
    Lease(NamespacedStore(store, "nb"), ttl_s=5.0).acquire(steal=True)
    time.sleep(0.2)              # past the holder's local horizon
    with pytest.raises(TxnError):
        s.run("set_val", name="x", val=1)
    view = NamespacedStore(store, "nb")
    assert view.get_meta("HEAD")["head"] == good
    assert txn.fsck(view).problems == 0


# ---------------------------------------------------------------------------
# tenant namespaces
# ---------------------------------------------------------------------------

def test_namespace_isolation_with_chunk_dedup():
    store = MemoryStore()
    sessions = {}
    for name in ("alice", "bob"):
        s = build_session(store, tenant=name)
        s.init_state({"a": np.arange(64, dtype=np.float32)})
        s.run("set_val", name="x", val=1)   # identical content per tenant
        sessions[name] = s
    assert sorted(tenant_ids(store)) == ["alice", "bob"]
    heads = {n: s.head for n, s in sessions.items()}
    # metadata is disjoint: each namespace sees only its own graph
    for name, s in sessions.items():
        assert sorted(s.graph.nodes) == sorted(
            n.split("/")[-1] for n in
            NamespacedStore(store, name).list_meta("commit/"))
    # chunks are shared: identical content deduped store-wide
    one = build_session(MemoryStore())
    one.init_state({"a": np.arange(64, dtype=np.float32)})
    one.run("set_val", name="x", val=1)
    assert store.n_chunks() == one.store.n_chunks()
    for s in (*sessions.values(), one):
        s.close()
    assert heads["alice"] == heads["bob"]   # same workload, same ids


def test_open_store_tenant_param():
    s = open_store("memory://?tenant=alice")
    assert isinstance(s, NamespacedStore) and s.meta_prefix == "tenant/alice/"
    with pytest.raises(ValueError):
        open_store("memory://?tenant=no/slashes")
    with pytest.raises(ValueError):
        validate_tenant_id("under_score")   # DirectoryStore maps _ specially
    with pytest.raises(ValueError):
        open_store("memory://?frobnicate=1")


def test_cross_tenant_gc_respects_shared_chunks():
    """alice and bob commit identical content (fully deduped); alice
    deleting her branch and gc'ing must reap nothing while bob still
    references the chunks — and bob's later gc reaps them for real."""
    store = MemoryStore()
    a = build_session(store, tenant="alice")
    b = build_session(store, tenant="bob")
    for s in (a, b):
        s.init_state({"a": np.arange(64, dtype=np.float32)})
        root = s.run("set_val", name="keep", val=1)
        s.run("set_val", name="drop", val=2)
        tip = s.head
        s.checkout(root)
        s.run("set_val", name="keep2", val=3)
        s._doomed = tip                      # branch to delete later
    n_before = store.n_chunks()
    assert a.delete_branch(a._doomed)
    out = a.gc()
    assert out["chunks_dropped"] == 0, \
        "alice reaped chunks bob's identical branch still references"
    assert store.n_chunks() == n_before
    assert b.delete_branch(b._doomed)
    out = b.gc()
    assert out["chunks_dropped"] > 0         # last reference gone: reap
    for s in (a, b):
        s.close()
    for tid, rep in txn.fsck_all(store).items():
        assert rep.problems == 0, (tid, rep.details)


def test_refcount_ledger_matches_commit_walk():
    store = MemoryStore()
    s = build_session(store)
    s.init_state({"a": np.arange(64, dtype=np.float32)})
    c1 = s.run("set_val", name="x", val=1)
    s.run("set_val", name="y", val=2)
    tip = s.head
    s.checkout(c1)
    s.run("set_val", name="y", val=7)
    s.delete_branch(tip)
    rebuilt = ChunkRefCounts.from_nodes(s.graph.nodes)
    assert s.graph.refs.counts == rebuilt.counts
    assert txn.fsck(store).refs_drift == 0
    # the ledger survives a reload and a gc
    s.gc()
    s.close()
    s2 = KishuSession(store, chunk_bytes=1 << 9)
    assert s2.graph.refs.counts == \
        ChunkRefCounts.from_nodes(s2.graph.nodes).counts
    s2.close()


def test_quota_blocks_commit_before_publish():
    store = MemoryStore()
    s = build_session(store, tenant="t", quota_bytes=1000)
    s.init_state({"a": np.arange(64, dtype=np.float32)})   # 256 B referenced
    # a constant-valued array dedups to ~2 unique chunks (~576 B logical)
    good = s.run("set_val", name="x", val=1)
    with pytest.raises(QuotaExceededError):
        s.run("set_val", name="y", val=2)                  # would cross 1000
    assert s.head == good                    # refused commit left no trace
    assert s.storage_stats()["tenant_ref_bytes"] <= 1000
    s.close()
    view = NamespacedStore(store, "t")
    assert txn.fsck(view).problems == 0


# ---------------------------------------------------------------------------
# kishud: admission queue, daemon, control socket
# ---------------------------------------------------------------------------

def test_admission_queue_interactive_before_background():
    q = AdmissionQueue(workers=1)
    order = []
    gate = threading.Event()
    blocker = q.submit(gate.wait)            # pins the only worker
    jb = q.submit(lambda: order.append("bg"), BACKGROUND)
    ji = q.submit(lambda: order.append("int"), INTERACTIVE)
    gate.set()
    ji.done.wait(5)
    jb.done.wait(5)
    blocker.done.wait(5)
    assert order == ["int", "bg"], \
        "background work was admitted ahead of interactive work"
    stats = q.stats()
    assert stats["served_interactive"] == 2    # blocker + ji
    assert stats["served_background"] == 1
    q.close()


def test_admission_queue_delivers_exceptions():
    q = AdmissionQueue(workers=1)
    with pytest.raises(ZeroDivisionError):
        q.run(lambda: 1 // 0)
    assert q.run(lambda: 41 + 1) == 42       # worker survived the raise
    q.close()


def test_kishud_multiplexes_tenants_with_shared_cache():
    d = Kishud(MemoryStore(), workers=2, lease_ttl_s=30.0,
               chunk_bytes=1 << 9)
    a = d.session("alice")
    b = d.session("bob")
    for s in (a, b):
        s.register("set_val", set_val)
        s.init_state({"a": np.arange(64, dtype=np.float32)})
    ca = a.run("set_val", name="x", val=1)
    cb = b.run("set_val", name="x", val=2)
    a.checkout(ca)
    b.checkout(cb)
    assert np.all(a.ns["x"] == 1.0) and np.all(b.ns["x"] == 2.0)
    st = d.status()
    assert st["n_sessions"] == 2 and st["tenants"] == ["alice", "bob"]
    assert st["queue"]["served_interactive"] >= 6
    rows = {r["tenant"]: r for r in d.tenants()}
    assert rows["alice"]["lease_owner"] != rows["bob"]["lease_owner"]
    assert rows["alice"]["n_commits"] == rows["bob"]["n_commits"] == 3
    d.close()


def _step(ns, seed):
    ns["w"][seed % len(ns["w"])] = float(seed)   # one dirty chunk per cell


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_kishud_tenants_commit_concurrently(n):
    """n tenants commit at once through one daemon over one store: every
    commit checks out bit-identical and every namespace fscks clean."""
    store = MemoryStore()
    d = Kishud(store, workers=4, lease_ttl_s=30.0, chunk_bytes=1 << 12)
    start = threading.Barrier(n, timeout=60)
    errors = []

    def tenant(tid):
        try:
            sess = d.session(f"t{tid}")
            sess.register("step", _step)
            sess.init_state({"w": np.zeros(1 << 13, np.float32)})
            start.wait()
            snaps = {}
            for i in range(6):
                cid = sess.run("step", seed=tid * 100 + i + 1)
                snaps[cid] = sess.ns["w"].copy()
            for cid, want in snaps.items():
                sess.checkout(cid)
                assert np.array_equal(sess.ns["w"], want), (tid, cid)
            sess.close()
        except Exception as e:                 # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=tenant, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    d.close()
    assert not errors, errors
    reports = txn.fsck_all(store)
    assert len(reports) >= n
    assert all(r.problems == 0 for r in reports.values()), \
        {t: r.details for t, r in reports.items() if r.problems}


@pytest.mark.parametrize("kind", ["memory", "dir"])
def test_kishud_two_writers_interleave(kind, tmp_path):
    """Two tenants interleave commits through one daemon: each commit
    checks out bit-identical, one tenant's gc reaps nothing the other
    still references, and both namespaces fsck clean."""
    store = (MemoryStore() if kind == "memory"
             else open_store(f"dir://{tmp_path}/cas"))
    d = Kishud(store, workers=2, lease_ttl_s=30.0, chunk_bytes=1 << 12)
    sessions, snaps = {}, {"alice": {}, "bob": {}}
    for name in snaps:
        s = d.session(name)
        s.register("step", _step)
        s.init_state({"w": np.zeros(1 << 12, np.float32)})
        sessions[name] = s
    for i in range(6):                       # a, b, a, b, ...
        name = "alice" if i % 2 == 0 else "bob"
        cid = sessions[name].run("step", seed=i + 1)
        snaps[name][cid] = sessions[name].ns["w"].copy()
    assert sessions["alice"].gc()["chunks_dropped"] == 0
    for name, s in sessions.items():
        for cid, want in snaps[name].items():
            s.checkout(cid)
            assert np.array_equal(s.ns["w"], want), (name, cid)
        s.close()
    d.close()
    reports = txn.fsck_all(store)
    assert all(r.problems == 0 for r in reports.values()), \
        {t: r.details for t, r in reports.items() if r.problems}


def test_session_takeover_after_killed_writer(tmp_path):
    """A writer dies holding its lease: a contender that will not wait is
    refused at once, a patient one takes over only after a full TTL, its
    checkout of the dead writer's last commit is bit-identical, and the
    store fscks clean after the successor's first commit."""
    uri = f"dir://{tmp_path}/cas"
    ttl = 0.3
    a = KishuSession(open_store(uri), tenant="nb", lease_ttl_s=ttl,
                     chunk_bytes=1 << 12)
    a.register("step", _step)
    a.init_state({"w": np.zeros(1 << 12, np.float32)})
    survivor = a.run("step", seed=7)
    want = a.ns["w"].copy()
    del a                                    # killed: lease doc left behind
    t0 = time.monotonic()
    with pytest.raises(LeaseHeld):
        KishuSession(open_store(uri), tenant="nb", lease_ttl_s=ttl)
    b = KishuSession(open_store(uri), tenant="nb", lease_ttl_s=ttl,
                     lease_wait_s=ttl * 10, chunk_bytes=1 << 12)
    assert time.monotonic() - t0 >= ttl
    b.register("step", _step)
    b.loader.materialize_state(b.tracked, b.graph.head)
    b.checkout(survivor)
    assert np.array_equal(b.ns["w"], want)
    b.run("step", seed=8)
    root = b.store.root_store
    b.close()
    assert all(r.problems == 0 for r in txn.fsck_all(root).values())


def test_kishud_session_survives_daemon_restart(tmp_path):
    uri = f"dir://{tmp_path}/cas"
    d = Kishud(uri, workers=1, lease_ttl_s=0.2, chunk_bytes=1 << 9)
    s = d.session("nb")
    s.register("set_val", set_val)
    s.init_state({"a": np.arange(64, dtype=np.float32)})
    cid = s.run("set_val", name="x", val=4)
    d.queue.close()              # simulated daemon death: no session close
    del d, s
    d2 = Kishud(uri, workers=1, lease_ttl_s=0.2, chunk_bytes=1 << 9)
    t0 = time.monotonic()
    s2 = d2.session("nb", lease_wait_s=10.0)   # steal after observed TTL
    assert time.monotonic() - t0 >= 0.2
    s2.register("set_val", set_val)
    assert s2.head == cid
    # a fresh session attaches with an empty live namespace: rehydrate
    s2.session.loader.materialize_state(s2.session.tracked, cid)
    assert np.all(s2.ns["x"] == 4.0)
    d2.close()


def test_kishud_socket_control(tmp_path):
    d = Kishud(MemoryStore(), workers=1, lease_ttl_s=30.0,
               chunk_bytes=1 << 9)
    sock = str(tmp_path / "kd.sock")
    srv = KishudServer(d, sock)
    try:
        assert control(sock, "ping")["pong"] is True
        s = d.session("alice")
        s.register("set_val", set_val)
        s.init_state({"a": np.arange(64, dtype=np.float32)})
        st = control(sock, "status")
        assert st["ok"] and st["tenants"] == ["alice"]
        tn = control(sock, "tenants")
        assert tn["tenants"][0]["tenant"] == "alice"
        assert tn["leases"][0]["owner"] is not None
        assert control(sock, "frobnicate")["ok"] is False
        assert control(sock, "stop")["stopping"] is True
        assert srv.wait(5)
    finally:
        srv.close()
        d.close()


# ---------------------------------------------------------------------------
# CLI verbs
# ---------------------------------------------------------------------------

def test_cli_lease_and_tenants_verbs(tmp_path, capsys):
    uri = f"dir://{tmp_path}/cas"
    store = open_store(uri)
    a = build_session(store, tenant="alice", lease_ttl_s=60.0)
    a.init_state({"a": np.arange(64, dtype=np.float32)})
    b = build_session(store, tenant="bob")
    b.init_state({"a": np.arange(64, dtype=np.float32)})
    b.close()

    assert cli(["--store", uri, "tenants"]) == 0
    out = capsys.readouterr().out
    assert "alice" in out and "bob" in out

    assert cli(["--store", f"{uri}?tenant=alice", "lease"]) == 0
    out = capsys.readouterr().out
    assert a.lease.owner in out
    assert cli(["--store", f"{uri}?tenant=alice", "lease",
                "--release", "writer"]) == 0
    capsys.readouterr()
    assert NamespacedStore(store, "alice").get_meta("lease/writer") is None
    a.close()                    # release of the already-dropped doc: no-op
