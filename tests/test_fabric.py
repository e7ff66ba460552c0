"""Storage fabric: hash ring, sharded scatter-gather, replicated
read-repair, tiered promotion/demotion, fabric:// topologies, fleet ops
(topology / scrub / rebalance), and session-level fault tolerance."""
import os
import shutil

import numpy as np
import pytest

from repro.core import (FaultInjectedStore, KishuSession, MemoryStore,
                        ReplicatedStore, ShardedStore, TieredStore,
                        open_store, rebalance, scrub)
from repro.core.chunkstore import DirectoryStore, chunk_key
from repro.core.fabric import HashRing, parse_size, parse_topology
from repro.core.serialize import ChunkMissingError
from repro.launch.kishu_cli import main as cli


def _pairs(n, tag="chunk"):
    out = []
    for i in range(n):
        d = f"{tag}-{i}".encode() * 7
        out.append((chunk_key(d), d))
    return out


# ---------------------------------------------------------------------------
# hash ring
# ---------------------------------------------------------------------------

def test_ring_deterministic_and_covering():
    r1, r2 = HashRing(4), HashRing(4)
    keys = [chunk_key(bytes([i & 255, i >> 8])) for i in range(1000)]
    homes = [r1.shard_for(k) for k in keys]
    assert homes == [r2.shard_for(k) for k in keys]      # deterministic
    counts = [homes.count(s) for s in range(4)]
    assert all(c > 100 for c in counts), counts          # roughly uniform


def test_ring_consistency_on_growth():
    """Adding one shard must move only a minority of keys (the consistent-
    hashing contract rebalance relies on)."""
    keys = [chunk_key(bytes([i & 255, i >> 8])) for i in range(2000)]
    r4, r5 = HashRing(4), HashRing(5)
    moved = sum(r4.shard_for(k) != r5.shard_for(k) for k in keys)
    assert 0 < moved < len(keys) // 2, moved


def test_ring_rejects_empty():
    with pytest.raises(ValueError):
        HashRing(0)


# ---------------------------------------------------------------------------
# sharded store
# ---------------------------------------------------------------------------

def test_sharded_roundtrip_and_placement():
    shards = [MemoryStore() for _ in range(4)]
    ss = ShardedStore(shards)
    pairs = _pairs(100)
    assert ss.put_chunks(pairs) == 100
    assert ss.get_chunks([k for k, _ in pairs]) == dict(pairs)
    assert ss.n_chunks() == 100
    assert sum(s.n_chunks() for s in shards) == 100      # no duplication
    assert all(s.n_chunks() > 0 for s in shards)         # all shards used
    for k, _ in pairs:                                   # ring placement
        assert shards[ss.home(k)].has_chunk(k)


def test_sharded_single_ops_and_missing():
    ss = ShardedStore([MemoryStore(), MemoryStore()])
    k, d = _pairs(1)[0]
    assert ss.put_chunk(k, d) is True
    assert ss.put_chunk(k, d) is False                   # CAS dedup
    assert ss.get_chunk(k) == d
    assert ss.has_chunk(k)
    with pytest.raises(ChunkMissingError):
        ss.get_chunk("f" * 32)
    assert ss.get_chunks(["f" * 32], missing_ok=True) == {}
    with pytest.raises(ChunkMissingError):
        ss.get_chunks([k, "f" * 32])


def test_sharded_stray_read_heals_placement():
    """A chunk sitting on the wrong shard (ring change, manual surgery) is
    served, copied home, and removed from the stray shard."""
    shards = [MemoryStore() for _ in range(3)]
    ss = ShardedStore(shards)
    k, d = _pairs(1, "stray")[0]
    stray = (ss.home(k) + 1) % 3
    shards[stray].put_chunk(k, d)
    assert ss.get_chunk(k) == d
    assert ss.heals == 1
    assert shards[ss.home(k)].has_chunk(k)
    assert not shards[stray].has_chunk(k)
    # batched path heals too
    k2, d2 = _pairs(1, "stray2")[0]
    stray2 = (ss.home(k2) + 1) % 3
    shards[stray2].put_chunk(k2, d2)
    assert ss.get_chunks([k, k2]) == {k: d, k2: d2}
    assert shards[ss.home(k2)].has_chunk(k2)
    assert not shards[stray2].has_chunk(k2)


def test_sharded_meta_mirrored_survives_shard_loss():
    shards = [MemoryStore() for _ in range(3)]
    ss = ShardedStore(shards)
    ss.put_meta("commit/c1", {"a": 1})
    ss.put_meta("HEAD", {"head": "c1"})
    shards[0].meta.clear()                               # lose one shard
    assert ss.get_meta("commit/c1") == {"a": 1}
    assert ss.list_meta("commit/") == ["commit/c1"]


def test_sharded_delete_sweeps_strays():
    shards = [MemoryStore() for _ in range(2)]
    ss = ShardedStore(shards)
    k, d = _pairs(1)[0]
    shards[0].put_chunk(k, d)
    shards[1].put_chunk(k, d)                            # stray copy too
    ss.delete_chunk(k)
    assert not any(s.has_chunk(k) for s in shards)
    pairs = _pairs(20)
    ss.put_chunks(pairs)
    assert ss.delete_chunks([k for k, _ in pairs]) == 20
    assert ss.n_chunks() == 0


# ---------------------------------------------------------------------------
# replicated store
# ---------------------------------------------------------------------------

def test_replicated_writes_land_everywhere():
    reps = [MemoryStore() for _ in range(3)]
    rs = ReplicatedStore(reps)
    pairs = _pairs(25)
    assert rs.put_chunks(pairs) == 25
    assert all(r.n_chunks() == 25 for r in reps)
    assert rs.n_chunks() == 25                           # logical, not 75


def test_replicated_read_repair_on_lost_replica():
    reps = [MemoryStore() for _ in range(2)]
    rs = ReplicatedStore(reps)
    pairs = _pairs(30)
    rs.put_chunks(pairs)
    reps[0].chunks.clear()                               # replica 0 dies
    assert rs.get_chunks([k for k, _ in pairs]) == dict(pairs)
    assert rs.replica_misses == 30
    assert rs.repairs == 30
    assert reps[0].n_chunks() == 30                      # healed in place
    assert scrub(rs).problems == 0


def test_replicated_serves_through_injected_fault():
    """FaultInjectedStore killing one replica: every read still succeeds."""
    healthy = MemoryStore()
    dead = FaultInjectedStore(MemoryStore(), fail_get=lambda k: True)
    rs = ReplicatedStore([dead, healthy])
    pairs = _pairs(10)
    rs.put_chunks(pairs)
    assert rs.get_chunk(pairs[0][0]) == pairs[0][1]
    assert rs.get_chunks([k for k, _ in pairs]) == dict(pairs)


def test_replicated_write_survives_dead_replica():
    """A replica whose writes *raise* (full/read-only disk) must not take
    down checkpointing: the write lands on the live replicas and the dead
    one heals later via read-repair/scrub."""
    class BrokenWrites(MemoryStore):
        def put_chunk(self, key, data):
            raise OSError("disk full")

        def put_chunks(self, pairs):
            raise OSError("disk full")

    healthy = MemoryStore()
    rs = ReplicatedStore([BrokenWrites(), healthy])
    pairs = _pairs(8)
    assert rs.put_chunks(pairs) == 8
    k, d = _pairs(1, "single")[0]
    assert rs.put_chunk(k, d) is True
    assert healthy.n_chunks() == 9
    assert rs.write_errors == 2
    assert rs.get_chunks([k for k, _ in pairs]) == dict(pairs)
    # every replica broken -> the write error surfaces
    rs_dead = ReplicatedStore([BrokenWrites(), BrokenWrites()])
    with pytest.raises(OSError):
        rs_dead.put_chunk(k, d)


def test_repair_and_heal_preserve_stored_compression():
    """Read-repair and stray-healing move chunks in *stored* form: a
    compressed chunk must stay compressed on the healed replica/shard."""
    from repro.core import CompressedStore
    data = b"Z" * 8192                                   # very compressible
    k = chunk_key(data)
    # replicated under an outer codec (the fabric://...?codec= shape)
    reps = [MemoryStore() for _ in range(2)]
    cs = CompressedStore(ReplicatedStore(reps), "zlib")
    cs.put_chunk(k, data)
    stored = reps[1].chunks[k]
    assert len(stored) < len(data)
    reps[0].chunks.clear()
    assert cs.get_chunk(k) == data                       # read-repairs
    assert reps[0].chunks[k] == stored                   # byte-identical copy
    # sharded stray heal
    shards = [MemoryStore() for _ in range(2)]
    ss = ShardedStore(shards)
    stray = (ss.home(k) + 1) % 2
    shards[stray].chunks[k] = stored                     # misplaced, framed
    assert ss.get_chunk(k) == data
    assert shards[ss.home(k)].chunks[k] == stored        # moved, still framed


def test_scrub_counts_logical_chunks_once():
    """chunks_checked reports logical chunks, not per-replica/per-level
    physical copies."""
    nested = ShardedStore([
        ReplicatedStore([MemoryStore(), MemoryStore()]),
        ReplicatedStore([MemoryStore(), MemoryStore()])])
    pairs = _pairs(40)
    nested.put_chunks(pairs)
    assert scrub(nested, deep=True).chunks_checked == 40
    assert scrub(nested).chunks_checked == 40


def test_replicated_lost_everywhere_raises():
    rs = ReplicatedStore([MemoryStore(), MemoryStore()])
    with pytest.raises(ChunkMissingError):
        rs.get_chunk("f" * 32)
    with pytest.raises(ChunkMissingError):
        rs.get_chunks(["f" * 32])
    assert rs.get_chunks(["f" * 32], missing_ok=True) == {}


def test_replicated_scrub_repair_heals_partial_loss():
    reps = [MemoryStore() for _ in range(3)]
    rs = ReplicatedStore(reps)
    pairs = _pairs(12)
    rs.put_chunks(pairs)
    for k, _ in pairs[:5]:
        reps[1].delete_chunk(k)
    rep = scrub(rs)
    assert rep.problems == 5 and rep.remaining == 5
    rep = scrub(rs, repair=True)
    assert rep.repaired == 5 and rep.remaining == 0
    assert scrub(rs).problems == 0
    assert all(r.n_chunks() == 12 for r in reps)


# ---------------------------------------------------------------------------
# tiered store
# ---------------------------------------------------------------------------

def test_tiered_write_through_and_promotion():
    cold = MemoryStore()
    ts = TieredStore(cold, hot_bytes=1 << 20)
    pairs = _pairs(10)
    ts.put_chunks(pairs)
    assert cold.n_chunks() == 10                         # durable on cold
    # hot hit: serve without touching cold
    cold.chunks.clear()
    assert ts.get_chunk(pairs[0][0]) == pairs[0][1]
    assert ts.get_chunks([k for k, _ in pairs]) == dict(pairs)


def test_tiered_promotes_on_read_and_bounds_hot():
    cold = MemoryStore()
    pairs = _pairs(50)
    cold_bytes = sum(len(d) for _, d in pairs)
    hot_cap = cold_bytes // 4
    ts = TieredStore(cold, hot_bytes=hot_cap)
    for k, d in pairs:
        cold.put_chunk(k, d)
    for k, d in pairs:                                   # reads promote
        assert ts.get_chunk(k) == d
    assert 0 < ts.hot.bytes_used <= hot_cap              # bounded demotion
    assert cold.n_chunks() == 50                         # demotion = drop


def test_tiered_delete_clears_both_tiers():
    cold = MemoryStore()
    ts = TieredStore(cold, hot_bytes=1 << 20)
    pairs = _pairs(6)
    ts.put_chunks(pairs)
    assert ts.delete_chunks([k for k, _ in pairs[:4]]) == 4
    assert ts.n_chunks() == 2
    for k, _ in pairs[:4]:
        assert not ts.has_chunk(k)
        with pytest.raises(ChunkMissingError):
            ts.get_chunk(k)


def test_tiered_hot_serves_logical_bytes_under_codec():
    """Hot tier caches decoded bytes: a compressed put must read back
    logical content from the hot tier."""
    from repro.core import CompressedStore
    cold = MemoryStore()
    ts = TieredStore(cold, hot_bytes=1 << 20)
    cs = CompressedStore(ts, "zlib")
    data = b"A" * 4096                                   # very compressible
    k = chunk_key(data)
    cs.put_chunk(k, data)
    assert cold.chunk_bytes_total() < len(data)          # stored compressed
    cold.chunks.clear()                                  # force hot path
    assert cs.get_chunk(k) == data


# ---------------------------------------------------------------------------
# topology specs
# ---------------------------------------------------------------------------

def test_parse_size():
    assert parse_size("4096") == 4096
    assert parse_size("64K") == 64 << 10
    assert parse_size("64M") == 64 << 20
    assert parse_size("1g") == 1 << 30
    with pytest.raises(ValueError):
        parse_size("lots")


def test_parse_topology_shapes(tmp_path):
    ss = parse_topology(f"shard(dir://{tmp_path}/a,dir://{tmp_path}/b)")
    assert isinstance(ss, ShardedStore) and len(ss.shards) == 2
    rs = parse_topology("rep(memory://,memory://,memory://)")
    assert isinstance(rs, ReplicatedStore) and len(rs.replicas) == 3
    ts = parse_topology(f"tier(64K,sqlite://{tmp_path}/c.db)")
    assert isinstance(ts, TieredStore) and ts.hot.max_bytes == 64 << 10
    nested = parse_topology("shard(rep(memory://,memory://),"
                            "rep(memory://,memory://))")
    assert isinstance(nested, ShardedStore)
    assert all(isinstance(c, ReplicatedStore) for c in nested.shards)


def test_parse_topology_errors():
    for bad in ("shard()", "rep()", "tier(64M)",
                "tier(64M,memory://,memory://)", "shard(memory://"):
        with pytest.raises(ValueError):
            parse_topology(bad)


def test_open_store_fabric_with_codec(tmp_path):
    from repro.core import CompressedStore
    st = open_store(f"fabric://shard(dir://{tmp_path}/s0,"
                    f"dir://{tmp_path}/s1)?codec=zlib")
    assert isinstance(st, CompressedStore)
    assert isinstance(st.inner, ShardedStore)
    data = os.urandom(100) + b"\x00" * 4000
    k = chunk_key(data)
    st.put_chunk(k, data)
    assert st.get_chunk(k) == data
    # readable without the codec suffix (frames decode transparently)
    st2 = open_store(f"fabric://shard(dir://{tmp_path}/s0,"
                     f"dir://{tmp_path}/s1)")
    assert st2.get_chunk(k) == data


# ---------------------------------------------------------------------------
# rebalance
# ---------------------------------------------------------------------------

def test_rebalance_after_adding_a_shard(tmp_path):
    pairs = _pairs(120)
    old = ShardedStore([DirectoryStore(str(tmp_path / "s0")),
                        DirectoryStore(str(tmp_path / "s1"))])
    old.put_chunks(pairs)
    # ring change: same dirs plus a fresh shard
    new = ShardedStore([DirectoryStore(str(tmp_path / "s0")),
                        DirectoryStore(str(tmp_path / "s1")),
                        DirectoryStore(str(tmp_path / "s2"))])
    out = rebalance(new)
    assert 0 < out["chunks_moved"] < len(pairs) // 2     # ~1/3 of the keys
    assert scrub(new).misplaced == 0
    assert new.shards[2].n_chunks() == out["chunks_moved"]
    assert new.get_chunks([k for k, _ in pairs]) == dict(pairs)


# ---------------------------------------------------------------------------
# session + CLI end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture
def fabric_session(tmp_path):
    uri = (f"fabric://shard(rep(dir://{tmp_path}/a0,dir://{tmp_path}/a1),"
           f"rep(dir://{tmp_path}/b0,dir://{tmp_path}/b1))")
    s = KishuSession(open_store(uri), chunk_bytes=1 << 10, cache_bytes=0)

    def set_val(ns, name, val):
        ns[name] = np.full(1500, float(val), np.float32)
    s.register("set_val", set_val)
    s.init_state({})
    c1 = s.run("set_val", name="x", val=1)
    s.run("set_val", name="x", val=2)
    s.close()
    return uri, s, c1, tmp_path


def _wipe_chunks(root):
    shutil.rmtree(os.path.join(root, "chunks"))
    os.makedirs(os.path.join(root, "chunks"))


def test_session_restores_with_one_replica_of_each_pair_down(fabric_session):
    uri, s, c1, tmp_path = fabric_session
    want = np.full(1500, 1.0, np.float32).tobytes()
    _wipe_chunks(str(tmp_path / "a0"))
    _wipe_chunks(str(tmp_path / "b1"))
    s2 = KishuSession(open_store(uri), chunk_bytes=1 << 10, cache_bytes=0)
    s2.checkout(c1)
    assert np.asarray(s2.ns["x"]).tobytes() == want      # bit-identical
    s2.close()
    # read-repair healed what checkout touched; scrub --repair the rest
    store = open_store(uri)
    scrub(store, repair=True)
    assert scrub(store).problems == 0


def _tenth_dirty_session(store):
    """Four 16-chunk arrays; each ``mutate`` rewrites 2 chunks of each."""
    s = KishuSession(store, chunk_bytes=1 << 12, cache_bytes=0)

    def init(ns, seed):
        rng = np.random.default_rng(seed)
        for i in range(4):
            ns[f"v{i}"] = rng.standard_normal(1 << 14).astype(np.float32)

    def mutate(ns, seed):
        rng = np.random.default_rng(seed)
        for i in range(4):
            for c in range(2):
                ns[f"v{i}"][c * 1024] = rng.standard_normal()
    s.register("init", init)
    s.register("mutate", mutate)
    s.init_state({})
    return s


def _snap(s):
    return {n: np.asarray(s.ns[n]).tobytes() for n in s.ns.names()}


@pytest.mark.parametrize("n_shards", [1, 4])
def test_session_hops_bit_identical_over_shards(n_shards, tmp_path):
    """Checkouts back and forth over a ring of directory stores restore
    the same bytes as over one directory store."""
    if n_shards == 1:
        store = DirectoryStore(str(tmp_path / "single"))
    else:
        store = ShardedStore([DirectoryStore(str(tmp_path / f"s{i}"))
                              for i in range(n_shards)])
    s = _tenth_dirty_session(store)
    prev = s.run("init", seed=1)
    prev_snap = _snap(s)
    for r in range(2):
        cur = s.run("mutate", seed=100 + r)
        cur_snap = _snap(s)
        st = s.checkout(prev)
        assert st.covs_patched == 4 and _snap(s) == prev_snap
        s.checkout(cur)
        assert _snap(s) == cur_snap
        prev, prev_snap = cur, cur_snap
    s.close()
    if n_shards > 1:
        assert all(sh.n_chunks() > 0 for sh in store.shards)


@pytest.mark.parametrize("loss", ["wiped", "fault_injected"])
def test_session_restores_through_replica_loss(loss, tmp_path):
    """One replica of two lost (its chunks wiped, or every read failing):
    checkout restores bit-identically from the survivor, and a repairing
    scrub leaves a deep scrub with 0 problems."""
    uri = f"fabric://rep(dir://{tmp_path}/r0,dir://{tmp_path}/r1)"
    s = _tenth_dirty_session(open_store(uri))
    c1 = s.run("init", seed=1)
    snap1 = _snap(s)
    s.run("mutate", seed=2)
    s.close()
    if loss == "wiped":
        _wipe_chunks(str(tmp_path / "r0"))
        store = open_store(uri)
    else:
        store = ReplicatedStore([
            FaultInjectedStore(DirectoryStore(str(tmp_path / "r0")),
                               fail_get=lambda k: True),
            DirectoryStore(str(tmp_path / "r1"))])
    s2 = _tenth_dirty_session(store)
    s2.checkout(c1)
    assert _snap(s2) == snap1
    s2.close()
    fresh = open_store(uri)
    scrub(fresh, repair=True)
    assert scrub(fresh, deep=True).problems == 0


def test_session_falls_back_to_recompute_when_lost_everywhere(tmp_path):
    """Chunk lost on ALL replicas -> DataRestorer recomputation still
    restores the state."""
    uri = f"fabric://rep(dir://{tmp_path}/r0,dir://{tmp_path}/r1)"
    s = KishuSession(open_store(uri), chunk_bytes=1 << 10, cache_bytes=0)

    def fill(ns, seed):
        rng = np.random.default_rng(seed)
        ns["x"] = rng.standard_normal(1000).astype(np.float32)
    s.register("fill", fill)
    s.init_state({})
    c1 = s.run("fill", seed=7)
    want = np.asarray(s.ns["x"]).tobytes()
    s.run("fill", seed=8)
    for root in ("r0", "r1"):
        _wipe_chunks(str(tmp_path / root))
    st = s.checkout(c1)
    assert st.covs_recomputed > 0
    assert np.asarray(s.ns["x"]).tobytes() == want
    s.close()


def test_session_gc_sweeps_all_shards_and_replicas(fabric_session):
    uri, s, c1, tmp_path = fabric_session
    store = open_store(uri)
    junk = _pairs(5, "junk")
    store.put_chunks(junk)                               # orphans
    s3 = KishuSession(open_store(uri), chunk_bytes=1 << 10)
    s3.register("set_val", lambda ns, name, val: None)
    out = s3.gc()
    assert out["chunks_dropped"] == 5
    for k, _ in junk:
        assert not store.has_chunk(k)
    s3.close()


def test_cli_fleet_verbs(fabric_session, capsys):
    uri, s, c1, tmp_path = fabric_session
    assert cli(["--store", uri, "topology"]) == 0
    out = capsys.readouterr().out
    assert "shard(n=2" in out and "rep(k=2)" in out
    assert cli(["--store", uri, "scrub", "--deep"]) == 0
    assert "0 problems" in capsys.readouterr().out
    assert cli(["--store", uri, "rebalance"]) == 0
    assert "moved 0" in capsys.readouterr().out
    # break a replica -> scrub reports, exit 2; --repair heals, exit 0
    _wipe_chunks(str(tmp_path / "a1"))
    assert cli(["--store", uri, "scrub"]) == 2
    assert "replica-missing" in capsys.readouterr().out
    assert cli(["--store", uri, "scrub", "--repair"]) == 0
    assert cli(["--store", uri, "scrub"]) == 0
    assert "0 problems" in capsys.readouterr().out


def test_cli_verify_and_log_on_fabric(fabric_session, capsys):
    uri, s, c1, _ = fabric_session
    assert cli(["--store", uri, "log"]) == 0
    assert "set_val" in capsys.readouterr().out
    assert cli(["--store", uri, "verify", "--deep"]) == 0
    assert "OK" in capsys.readouterr().out
