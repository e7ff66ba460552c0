"""Parallel chunk I/O engine: batched store ops, bit-identical parallel
checkout on every backend, and fault/latency injection under parallel fetch
(chunk loss -> fallback recomputation, slow hosts -> bandwidth not
round-trips; never crashes or deadlocks)."""
import time

import numpy as np
import pytest

from repro.core import (FaultInjectedStore, KishuSession, MemoryStore,
                        ChunkMissingError)
from repro.core.chunkstore import (DirectoryStore, SQLiteStore, chunk_key)
from repro.core import parallel


def make_store(kind, tmp_path):
    if kind == "memory":
        return MemoryStore()
    if kind == "dir":
        return DirectoryStore(str(tmp_path / "cas"))
    return SQLiteStore(str(tmp_path / "cas.db"))


@pytest.fixture(params=["memory", "dir", "sqlite"])
def store(request, tmp_path):
    return make_store(request.param, tmp_path)


# ---------------------------------------------------------------------------
# batched backend ops
# ---------------------------------------------------------------------------

def test_put_get_chunks_roundtrip(store):
    pairs = [(chunk_key(bytes([i]) * 100), bytes([i]) * 100)
             for i in range(20)]
    assert store.put_chunks(pairs) == 20
    assert store.put_chunks(pairs) == 0            # CAS dedup, batched
    got = store.get_chunks([k for k, _ in pairs])
    assert got == dict(pairs)
    assert sorted(store.list_chunk_keys()) == sorted(k for k, _ in pairs)


def test_get_chunks_missing(store):
    k = chunk_key(b"present")
    store.put_chunk(k, b"present")
    ghost = "deadbeef" * 4
    assert store.get_chunks([k, ghost], missing_ok=True) == {k: b"present"}
    with pytest.raises(ChunkMissingError):
        store.get_chunks([k, ghost])


def test_get_chunks_duplicate_keys(store):
    k = chunk_key(b"x" * 50)
    store.put_chunk(k, b"x" * 50)
    assert store.get_chunks([k, k, k]) == {k: b"x" * 50}


def test_list_chunk_keys_empty(store):
    assert store.list_chunk_keys() == []


def test_chunk_sizes(store):
    pairs = [(chunk_key(bytes([i]) * (10 + i)), bytes([i]) * (10 + i))
             for i in range(5)]
    store.put_chunks(pairs)
    sizes = store.chunk_sizes([k for k, _ in pairs] + ["feedbeef" * 4])
    assert sizes == {k: len(d) for k, d in pairs}


def test_fault_wrapper_forwards_engine_hints(tmp_path):
    sq = FaultInjectedStore(SQLiteStore(str(tmp_path / "h.db")))
    assert sq.min_slab == SQLiteStore.min_slab
    assert sq.supports_parallel_get
    mem = FaultInjectedStore(MemoryStore())
    assert not mem.supports_parallel_get       # RAM: nothing to overlap
    slow = FaultInjectedStore(MemoryStore(), read_delay=0.001)
    assert slow.supports_parallel_get          # injected round trip


def test_sqlite_batch_larger_than_in_clause_limit(tmp_path):
    store = SQLiteStore(str(tmp_path / "big.db"))
    pairs = [(chunk_key(str(i).encode()), str(i).encode())
             for i in range(1203)]                  # > 2 x _SQL_BATCH
    assert store.put_chunks(pairs) == len(pairs)
    got = store.get_chunks([k for k, _ in pairs])
    assert len(got) == len(pairs)


# ---------------------------------------------------------------------------
# parallel executor primitives
# ---------------------------------------------------------------------------

def test_prefetch_map_yields_all_results():
    out = sorted(parallel.prefetch_map(lambda x: x * 2, range(50), 8))
    assert out == [x * 2 for x in range(50)]


def test_prefetch_map_serial_fallback():
    assert list(parallel.prefetch_map(lambda x: x + 1, [1, 2, 3], 1)) \
        == [2, 3, 4]


def test_prefetch_map_propagates_exceptions():
    def boom(x):
        if x == 7:
            raise ValueError("x7")
        return x
    with pytest.raises(ValueError):
        list(parallel.prefetch_map(boom, range(20), 4))


def test_map_parallel_ordered():
    assert parallel.map_parallel(lambda x: -x, list(range(40)), 8) \
        == [-x for x in range(40)]


def test_no_nested_pools():
    def outer(_):
        assert parallel.in_io_worker()
        # nested call must degrade to serial, not spawn another pool
        return parallel.map_parallel(lambda y: y, [1, 2, 3], 8)
    assert parallel.map_parallel(outer, [0, 1], 2) == [[1, 2, 3]] * 2


def test_iter_slabs_preserve_order():
    slabs = list(parallel.iter_slabs(list(range(10)), 4))
    assert slabs == [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]


# ---------------------------------------------------------------------------
# parallel checkout == serial checkout, bit for bit, on all backends
# ---------------------------------------------------------------------------

N_VARS = 6
N_ELEMS = 4000          # x float32 = 16 KB -> 16 chunks at 1 KB each


def build_session(store, io_threads):
    # cache_bytes=0: these tests measure the *backend* I/O engine; the
    # shared chunk cache would serve just-written chunks from memory
    s = KishuSession(store, chunk_bytes=1 << 10, io_threads=io_threads,
                     cache_bytes=0)
    s.loader.probe_threshold_s = 0.0     # always engage the pipeline

    def step(ns, seed):
        rng = np.random.default_rng(seed)
        for i in range(N_VARS):
            ns[f"v{i}"] = rng.standard_normal(N_ELEMS).astype(np.float32)
    s.register("step", step)
    s.init_state({})
    return s


def snapshot(sess):
    return {n: np.asarray(sess.ns[n]).tobytes() for n in sess.ns.names()}


def test_parallel_checkout_bit_identical_to_serial(store):
    s = build_session(store, io_threads=8)
    c1 = s.run("step", seed=1)
    c2 = s.run("step", seed=2)

    s.loader.io_threads = 1                  # serial reference restore
    s.checkout(c1)
    ref = snapshot(s)
    s.checkout(c2)

    s.loader.io_threads = 8                  # engine restore
    st = s.checkout(c1)
    assert snapshot(s) == ref
    assert st.covs_loaded == N_VARS and st.covs_recomputed == 0
    assert st.bytes_loaded == N_VARS * N_ELEMS * 4


def test_parallel_checkout_deterministic_across_runs(store):
    s = build_session(store, io_threads=8)
    c1 = s.run("step", seed=1)
    c2 = s.run("step", seed=2)
    snaps = []
    for _ in range(3):
        s.checkout(c1)
        snaps.append(snapshot(s))
        s.checkout(c2)
    assert snaps[0] == snaps[1] == snaps[2]


def test_materialize_state_parallel(store):
    s = build_session(store, io_threads=8)
    c1 = s.run("step", seed=3)
    s.run("step", seed=4)
    s.loader.io_threads = 1
    s.loader.materialize_state(s.tracked, c1)
    ref = snapshot(s)
    s.loader.io_threads = 8
    from repro.core.namespace import Namespace, TrackedNamespace
    fresh = TrackedNamespace(Namespace())
    records, st = s.loader.materialize_state(fresh, c1)
    assert {n: np.asarray(fresh.base[n]).tobytes()
            for n in fresh.base.names()} == ref
    assert set(records) == set(f"v{i}" for i in range(N_VARS))


# ---------------------------------------------------------------------------
# fault injection under parallel fetch
# ---------------------------------------------------------------------------

def chunk_keys_of(sess, commit):
    out = []
    for man in sess.graph.nodes[commit].manifests.values():
        if man.get("unserializable"):
            continue
        out.extend(c["key"] for c in man["base"]["chunks"])
    return out


def test_chunk_loss_falls_back_to_recompute():
    bad = set()
    # read_delay: a slow host, so the wrapper advertises parallel fetch and
    # the loss is hit inside the pipeline, not the serial path
    store = FaultInjectedStore(MemoryStore(), fail_get=lambda k: k in bad,
                               read_delay=0.0005)
    s = build_session(store, io_threads=8)
    c1 = s.run("step", seed=1)
    c2 = s.run("step", seed=2)

    s.checkout(c1)
    ref = snapshot(s)
    s.checkout(c2)

    lost = chunk_keys_of(s, c1)
    bad.update(lost[::3])                    # drop a third of c1's chunks
    st = s.checkout(c1)
    assert snapshot(s) == ref                # recomputed, still bit-exact
    assert st.covs_recomputed > 0


def test_total_chunk_loss_still_restores():
    bad = set()
    store = FaultInjectedStore(MemoryStore(), fail_get=lambda k: k in bad,
                               read_delay=0.0005)
    s = build_session(store, io_threads=8)
    c1 = s.run("step", seed=5)
    c2 = s.run("step", seed=6)
    s.checkout(c1)
    ref = snapshot(s)
    s.checkout(c2)
    bad.update(chunk_keys_of(s, c1))         # every chunk of the target
    st = s.checkout(c1)
    assert snapshot(s) == ref
    assert st.covs_recomputed == N_VARS


def test_slow_host_parallel_fetch_beats_serial():
    """Per-chunk read latency dominates: the engine overlaps it; must also
    stay bit-exact and finish (no deadlock under delay injection)."""
    delay = 0.004
    store = FaultInjectedStore(MemoryStore(), read_delay=delay)
    s = build_session(store, io_threads=8)
    c1 = s.run("step", seed=1)
    c2 = s.run("step", seed=2)

    s.loader.io_threads = 1
    t0 = time.perf_counter()
    s.checkout(c1)
    serial_s = time.perf_counter() - t0
    ref = snapshot(s)
    s.checkout(c2)

    s.loader.io_threads = 8
    t0 = time.perf_counter()
    s.checkout(c1)
    parallel_s = time.perf_counter() - t0
    assert snapshot(s) == ref
    # ~96 chunks x 4ms serial vs 8-way overlap: generous 0.6 margin
    assert parallel_s < serial_s * 0.6, (serial_s, parallel_s)


def test_slow_host_with_chunk_loss_no_deadlock():
    bad = set()
    store = FaultInjectedStore(MemoryStore(), read_delay=0.002,
                               fail_get=lambda k: k in bad)
    s = build_session(store, io_threads=8)
    c1 = s.run("step", seed=7)
    c2 = s.run("step", seed=8)
    s.checkout(c1)
    ref = snapshot(s)
    s.checkout(c2)
    bad.update(chunk_keys_of(s, c1)[::5])
    st = s.checkout(c1)                      # completes: no deadlock
    assert snapshot(s) == ref
    assert st.covs_recomputed > 0


# ---------------------------------------------------------------------------
# adaptive engagement probe
# ---------------------------------------------------------------------------

def pipeline_spy(monkeypatch):
    calls = []
    real = parallel.prefetch_map

    def spy(fn, items, max_workers=None, window=None):
        calls.append(True)
        return real(fn, items, max_workers, window)
    monkeypatch.setattr(parallel, "prefetch_map", spy)
    return calls


def test_probe_engages_pipeline_on_slow_store(monkeypatch):
    calls = pipeline_spy(monkeypatch)
    store = FaultInjectedStore(MemoryStore(), read_delay=0.005)
    s = build_session(store, io_threads=4)
    s.loader.probe_threshold_s = 1e-3    # default adaptive threshold
    c1 = s.run("step", seed=1)
    s.run("step", seed=2)
    calls.clear()                        # the commits key on the pool
    s.checkout(c1)
    assert calls                         # 5ms/chunk >> threshold: parallel


def test_probe_stays_serial_on_fast_store(monkeypatch):
    calls = pipeline_spy(monkeypatch)
    # tiny delay keeps the wrapper parallel-capable; the threshold decides
    store = FaultInjectedStore(MemoryStore(), read_delay=1e-5)
    s = build_session(store, io_threads=4)
    s.loader.probe_threshold_s = float("inf")     # force bandwidth-bound
    c1 = s.run("step", seed=1)
    s.run("step", seed=2)
    calls.clear()                        # the commits key on the pool
    st = s.checkout(c1)
    assert not calls                     # degraded to serial slab loop
    assert st.covs_loaded == N_VARS      # ...and still restored everything


# ---------------------------------------------------------------------------
# batched writer
# ---------------------------------------------------------------------------

def test_sync_write_durable_on_return(store):
    s = build_session(store, io_threads=8)
    c1 = s.run("step", seed=1)
    for k in chunk_keys_of(s, c1):           # batch landed before run returned
        assert store.has_chunk(k)


def test_async_write_batched_drain(store):
    s = KishuSession(store, chunk_bytes=1 << 10, async_write=True,
                     io_threads=8)

    def step(ns, seed):
        rng = np.random.default_rng(seed)
        for i in range(N_VARS):
            ns[f"v{i}"] = rng.standard_normal(N_ELEMS).astype(np.float32)
    s.register("step", step)
    s.init_state({})
    c1 = s.run("step", seed=1)
    c2 = s.run("step", seed=2)
    s.writer.flush()
    for k in chunk_keys_of(s, c1) + chunk_keys_of(s, c2):
        assert store.has_chunk(k)
    s.checkout(c1)
    assert float(np.asarray(s.ns["v0"])[0]) == pytest.approx(float(
        np.random.default_rng(1).standard_normal(N_ELEMS).astype(
            np.float32)[0]))
    s.close()


def test_writer_no_double_write_within_delta():
    """Identical content appearing twice in one delta is written once even
    though puts are deferred into the batch."""
    store = MemoryStore()
    s = KishuSession(store, chunk_bytes=1 << 10)

    def twins(ns):
        ns["a"] = np.ones(N_ELEMS, np.float32)
        ns["b"] = np.ones(N_ELEMS, np.float32)   # same bytes, distinct cov
    s.register("twins", twins)
    s.init_state({})
    s.run("twins")
    ws = s.last_run.write
    assert ws.chunks_dedup > 0
    assert ws.chunks_written * (1 << 10) <= N_ELEMS * 4
