"""Cost-based checkout planner (DESIGN.md §18): pricing, mode resolution,
parity of planner-on checkout with the fixed ladder on every backend, the
covs_recomputed single-count contract, and the bounded replay memo."""
import os
import time

import numpy as np
import pytest

from repro.core import (CheckoutPlanner, DetReplaySession, KishuSession,
                        MemoryStore, PricedPlan, StoreCostModel, format_plan,
                        open_store, resolve_plan_mode)
from repro.core.chunkstore import ChunkCache, DirectoryStore, SQLiteStore
from repro.core.planner import INF
from repro.core.restore import DataRestorer, resolve_memo_bytes
from repro.obs.metrics import MetricsRegistry


def make_store(kind, tmp_path):
    if kind == "memory":
        return MemoryStore()
    tmp_path.mkdir(parents=True, exist_ok=True)
    if kind == "dir":
        return DirectoryStore(str(tmp_path / "cas"))
    if kind == "sqlite":
        return SQLiteStore(str(tmp_path / "cas.db"))
    return open_store(f"fabric://shard(dir://{tmp_path}/s0,"
                      f"dir://{tmp_path}/s1)")


def build_session(store, **kw):
    kw.setdefault("chunk_bytes", 256)
    s = KishuSession(store, **kw)
    s.register("step", _step)
    s.register("derive", _derive)
    return s


def _step(ns, k=1.0):
    ns["w"] = ns["w"] + np.float32(k)


def _derive(ns, scale=1.0):
    ns["big"] = (np.arange(512, dtype=np.float32)
                 * ns["seed"].sum() * np.float32(scale))


def run_workload(s):
    cids = [s.init_state({"w": np.zeros(256, np.float32),
                          "seed": np.arange(4, dtype=np.float32)})]
    for k in range(1, 4):
        cids.append(s.run("step", k=float(k)))
        cids.append(s.run("derive", scale=float(k)))
    return cids


# ---------------------------------------------------------------------------
# mode resolution
# ---------------------------------------------------------------------------

def test_resolve_plan_mode_arg_env_default(monkeypatch):
    assert resolve_plan_mode(None) == "off"
    monkeypatch.setenv("KISHU_PLANNER", "auto")
    assert resolve_plan_mode(None) == "auto"
    assert resolve_plan_mode("off") == "off"       # arg wins over env
    monkeypatch.setenv("KISHU_PLANNER", "1")
    assert resolve_plan_mode(None) == "auto"
    assert resolve_plan_mode("forced-replay") == "replay"
    assert resolve_plan_mode("forced-fetch") == "fetch"
    with pytest.raises(ValueError):
        resolve_plan_mode("bogus")


# ---------------------------------------------------------------------------
# store cost model
# ---------------------------------------------------------------------------

def test_cost_model_cold_defaults():
    m = StoreCostModel(None)
    lat, bw, n = m.snapshot()
    assert n == 0 and lat > 0 and bw > 0
    assert m.fetch_seconds(0, 0) == 0.0
    assert m.fetch_seconds(1 << 20, 4) > 0


def test_cost_model_reads_store_metrics():
    reg = MetricsRegistry()
    h = reg.histogram("kishu_store_op_seconds", op="get_chunks",
                      backend="memory")
    for _ in range(10):
        h.observe(0.01)                  # 10 ops x 10ms
    reg.counter("kishu_store_bytes_total", dir="get",
                backend="memory").inc(1_000_000)
    m = StoreCostModel(reg)
    lat, bw, n = m.snapshot()
    assert n == 10
    assert lat == pytest.approx(0.01)
    assert bw == pytest.approx(1_000_000 / 0.1)
    # 1MB at 10MB/s ~ 0.1s plus one op latency
    assert m.fetch_seconds(1_000_000, 3) == pytest.approx(0.11, rel=0.05)


# ---------------------------------------------------------------------------
# pricing
# ---------------------------------------------------------------------------

def test_plan_prices_and_formats(tmp_path):
    s = build_session(MemoryStore(), plan_mode="auto", cache_bytes=0)
    cids = run_workload(s)
    p = s.plan(cids[2])
    assert isinstance(p, PricedPlan)
    assert p.target == cids[2] and p.mode == "auto"
    assert p.covs, "diverged covs must be priced"
    for c in p.covs:
        assert c.path in ("fetch", "replay", "patch")
        assert c.fetch_s < INF           # everything serializable here
    text = "\n".join(format_plan(p))
    assert cids[2] in text and "store model" in text
    s.close()


def test_cache_resident_bytes_price_zero():
    s = build_session(MemoryStore(), plan_mode="auto")   # default cache on
    cids = run_workload(s)
    p = s.plan(cids[-2])
    # every chunk was just written through the shared cache
    fetchable = [c for c in p.covs if c.path != "replay"]
    assert fetchable and all(c.est_bytes == 0 for c in fetchable)
    s.close()


def test_replay_shared_ancestor_priced_once():
    """Two co-variables produced by the same commit charge its exec once."""
    store = MemoryStore()
    s = KishuSession(store, plan_mode="auto", cache_bytes=0, chunk_bytes=256)

    def pair(ns, k=1.0):
        ns["a"] = np.full(64, np.float32(k))
        ns["b"] = np.full(64, np.float32(-k))
    s.register("pair", pair)
    s.init_state({"seed": np.arange(4, dtype=np.float32)})
    c1 = s.run("pair", k=1.0)
    s.run("pair", k=2.0)
    planner = s.planner
    charged = set()
    cost_a, closure_a, _ = planner._replay_price(c1, charged)
    assert cost_a < INF and closure_a
    charged |= closure_a
    cost_b, closure_b, _ = planner._replay_price(c1, charged)
    assert cost_b == 0.0 and not closure_b   # memo-shared: free second time
    s.close()


def test_unregistered_and_unsafe_commands_never_replay():
    s = build_session(MemoryStore(), plan_mode="replay", cache_bytes=0)
    s.register("sideeffect", lambda ns, v=1.0: ns.__setitem__(
        "x", np.full(8, np.float32(v))), replay_safe=False)
    run_workload(s)
    cx = s.run("sideeffect", v=1.0)
    s.run("sideeffect", v=2.0)           # x diverges between HEAD and cx
    p = s.plan(cx)
    x_plan = [c for c in p.covs if "x" in c.key]
    assert x_plan and x_plan[0].path != "replay"
    assert x_plan[0].replay_s == INF
    # and the flag is persisted in the commit doc
    assert s.graph.nodes[cx].stats["replay_safe"] is False
    s.close()


def test_forced_replay_routes_replayable_covs():
    s = build_session(MemoryStore(), plan_mode="replay", cache_bytes=0)
    cids = run_workload(s)
    st = s.checkout(cids[-3])
    assert st.covs_planned_replay > 0
    assert st.covs_recomputed == st.covs_planned_replay
    s.close()


# ---------------------------------------------------------------------------
# parity: planner on == planner off, bit for bit, on every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["memory", "dir", "sqlite", "fabric"])
@pytest.mark.parametrize("mode", ["auto", "fetch", "replay"])
def test_planner_parity(kind, mode, tmp_path):
    base = build_session(make_store(kind, tmp_path / "off"), plan_mode="off",
                         cache_bytes=0)
    plnd = build_session(make_store(kind, tmp_path / mode), plan_mode=mode,
                         cache_bytes=0)
    cids_a = run_workload(base)
    cids_b = run_workload(plnd)
    assert cids_a == cids_b
    for target in (cids_a[2], cids_a[-1], cids_a[1]):
        base.checkout(target)
        plnd.checkout(target)
        assert sorted(base.ns.names()) == sorted(plnd.ns.names())
        for name in base.ns.names():
            a, b = np.asarray(base.ns[name]), np.asarray(plnd.ns[name])
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(a, b), (name, target)
        # same chunk keys: the graphs must reference identical manifests
        na, nb = base.graph.nodes[target], plnd.graph.nodes[target]
        for ks in na.state_index:
            assert na.state_index[ks] == nb.state_index[ks]
    base.close()
    plnd.close()


def test_plan_matches_executed_paths():
    s = build_session(MemoryStore(), plan_mode="auto", cache_bytes=0)
    cids = run_workload(s)
    target = cids[-3]
    p = s.plan(target)
    st = s.checkout(target)
    n = p.counts()
    assert st.covs_planned_fetch == n["fetch"]
    assert st.covs_planned_patch == n["patch"]
    assert st.covs_planned_replay == n["replay"]
    assert st.plan_est_s == pytest.approx(p.est_total_s, rel=0.5, abs=1.0)
    s.close()


def _dirty_rate_session(path, mode, dirty_chunks):
    """``w`` (64 chunks) dirtied in place, ``big`` derived from the
    one-chunk ``seed``: the patch lane and the replay lane both apply."""
    s = KishuSession(DirectoryStore(path), chunk_bytes=1 << 10,
                     cache_bytes=0, plan_mode=mode)

    def touch(ns, step):
        for c in range(dirty_chunks):
            ns["w"][c * 256] = np.float32(step * 1000 + c)

    def derive(ns, scale):
        ns["big"] = (np.arange(1 << 14, dtype=np.float32)
                     + ns["seed"].sum()) * np.float32(scale)
    s.register("touch", touch)
    s.register("derive", derive)
    s.init_state({"w": np.arange(1 << 14, dtype=np.float32),
                  "seed": np.linspace(0.0, 1.0, 256).astype(np.float32)})
    ids = []
    for r in range(1, 4):
        s.run("touch", step=r)
        ids.append(s.run("derive", scale=float(r)))
    return s, ids


def _state(s):
    return {n: np.asarray(s.ns[n]).tobytes() for n in s.ns.names()}


@pytest.mark.parametrize("dirty_frac", [0.01, 0.1, 0.5])
def test_planner_auto_matches_off_by_dirty_rate(dirty_frac, tmp_path):
    """At every dirty rate, planner-auto restores what fetch-only
    restores, both stores hold the same chunk keys, and the priced plan's
    path counts are the executed checkout's ``covs_planned_*``."""
    dirty_chunks = max(1, round(64 * dirty_frac))
    runs = {}
    for mode in ("off", "auto"):
        path = str(tmp_path / mode)
        s, ids = _dirty_rate_session(path, mode, dirty_chunks)
        target, head = ids[0], ids[-1]
        states = []
        for _ in range(2):
            if mode == "auto":
                want = s.plan(target).counts()
            st = s.checkout(target)
            if mode == "auto":
                assert want == {"fetch": st.covs_planned_fetch,
                                "replay": st.covs_planned_replay,
                                "patch": st.covs_planned_patch}
            states.append(_state(s))
            s.checkout(head)
            states.append(_state(s))
        s.close()
        assert states[0] == states[2] and states[1] == states[3]
        runs[mode] = (states, sorted(DirectoryStore(path).list_chunk_keys()))
    assert runs["off"] == runs["auto"]


def test_det_replay_prices_fetch_at_infinity():
    """DetReplay's skipped commits are unserializable: the planner must
    price fetch at infinity and still checkout bit-identically."""
    store = MemoryStore()
    s = DetReplaySession(store, plan_mode="auto", cache_bytes=0,
                         chunk_bytes=256)
    s.register("det", lambda ns, k=1.0: ns.__setitem__(
        "w", ns["w"] * np.float32(k)), deterministic=True)
    c0 = s.init_state({"w": np.arange(128, dtype=np.float32)})
    c1 = s.run("det", k=2.0)
    c2 = s.run("det", k=3.0)
    p = s.plan(c1)
    w_plan = [c for c in p.covs if "w" in c.key]
    assert w_plan and w_plan[0].fetch_s == INF
    assert w_plan[0].path == "replay"
    st = s.checkout(c1)
    assert np.array_equal(s.ns["w"], np.arange(128, dtype=np.float32) * 2.0)
    assert st.covs_recomputed >= 1
    s.close()


# ---------------------------------------------------------------------------
# covs_recomputed: one count per replayed co-variable (satellite audit)
# ---------------------------------------------------------------------------

def test_covs_recomputed_three_deep_chain():
    """3-deep dependency chain with every chunk wiped from the store:
    checkout restores a/b/c via recursive replay (root replays too, as the
    chain's dependency).  covs_recomputed must count each distinct
    versioned co-variable exactly once — the old accounting incremented at
    both the loader call sites and inside the recursion, double-counting
    every intermediate link."""
    store = MemoryStore()
    s = KishuSession(store, chunk_bytes=256, cache_bytes=0)
    def mk(ns, name, dep):
        ns[name] = ns[dep] + np.float32(1)
    s.register("mk", mk)
    c0 = s.init_state({"root": np.zeros(64, np.float32)})
    s.run("mk", name="a", dep="root")
    s.run("mk", name="b", dep="a")
    c3 = s.run("mk", name="c", dep="b")
    # wipe the CAS: every load now falls back to replay
    store.delete_chunks(list(store.list_chunk_keys()))
    st = s.checkout(c0)
    assert st.covs_recomputed == 0       # deletes only, nothing restored
    st = s.checkout(c3)
    # distinct versioned covs restored via replay: a@c1, b@c2, c@c3, plus
    # root@c0 replayed as the chain's root dependency = 4.  (The old
    # double-counting reported 6 on this shape.)
    assert st.covs_recomputed == 4
    assert np.array_equal(s.ns["c"], np.full(64, np.float32(3)))
    s.close()


# ---------------------------------------------------------------------------
# replay memo: bound + partial-hit top-up (satellite)
# ---------------------------------------------------------------------------

def test_resolve_memo_bytes(monkeypatch):
    assert resolve_memo_bytes(123) == 123
    monkeypatch.setenv("KISHU_RESTORE_MEMO_BYTES", "4096")
    assert resolve_memo_bytes() == 4096
    monkeypatch.setenv("KISHU_RESTORE_MEMO_BYTES", "junk")
    assert resolve_memo_bytes() == 256 << 20
    monkeypatch.delenv("KISHU_RESTORE_MEMO_BYTES")
    assert resolve_memo_bytes() == 256 << 20


def test_memo_bounded_eviction(monkeypatch):
    monkeypatch.setenv("KISHU_RESTORE_MEMO_BYTES", "1024")
    s = KishuSession(MemoryStore(), chunk_bytes=256, cache_bytes=0)
    assert s.restorer.memo_bytes == 1024

    class Opaque:
        def __init__(self, v):
            self.v = v
    def grow(ns, k=0):
        ns[f"o{k}"] = Opaque(k)
        ns["carry"] = np.full(256, np.float32(k))   # 1 KiB per namespace
    s.register("grow", grow)
    s.init_state({"carry": np.zeros(256, np.float32)})
    last = None
    for k in range(6):
        last = s.run("grow", k=k)
    s.checkout(s.graph.path_from_root(last)[0])
    s.checkout(last)                     # replays the opaque chain
    # the memo held at most ~1 KiB worth of namespaces (plus the floor of
    # one entry), not all six replayed states
    assert len(s.restorer._memo) <= 2
    s.close()


def test_memo_partial_hit_tops_up_without_rerun():
    """A memoized replay missing a requested name is topped up from the
    commit's state index — the command must NOT run again."""
    s = KishuSession(MemoryStore(), chunk_bytes=256, cache_bytes=0)
    runs = {"n": 0}
    def two(ns, k=1.0):
        runs["n"] += 1
        ns["p"] = np.full(16, np.float32(k))
        ns["q"] = np.full(16, np.float32(-k))
    s.register("two", two)
    s.init_state({"seed": np.zeros(4, np.float32)})
    c1 = s.run("two", k=5.0)
    before = runs["n"]
    # replay once to seed the memo
    got = s.restorer.recompute(("p",), c1, None)
    assert runs["n"] == before + 1
    # simulate a partial namespace (regrouped request): drop q from the memo
    memo_ns = s.restorer._memo[c1]
    del memo_ns["q"]
    got = s.restorer.recompute(("q",), c1, None)
    assert np.array_equal(got["q"], np.full(16, np.float32(-5.0)))
    assert runs["n"] == before + 1       # topped up from the store, no rerun
    s.close()


def test_replay_count_in_log():
    s = build_session(MemoryStore(), plan_mode="replay", cache_bytes=0)
    cids = run_workload(s)
    s.checkout(cids[1])
    entries = {e["commit"]: e for e in s.log()}
    assert all("exec_s" in e and "replays" in e for e in entries.values())
    assert sum(e["replays"] for e in entries.values()) == s.restorer.replays
    assert any(e["replays"] > 0 for e in entries.values())
    s.close()


# ---------------------------------------------------------------------------
# ChunkCache.contains: non-mutating probe
# ---------------------------------------------------------------------------

def test_cache_contains_no_side_effects():
    c = ChunkCache(1 << 16)
    c.put("k1", b"x" * 100)
    h0, m0 = c.hits, c.misses
    assert c.contains("k1") and not c.contains("nope")
    assert (c.hits, c.misses) == (h0, m0)
    assert ChunkCache(0).contains("k1") is False
