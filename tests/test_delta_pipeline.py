"""Chunk-granular delta pipeline: dirty-range serialization, patch
checkout, codec round-trips, and writer<->loader cache coherence."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (CompressedStore, FaultInjectedStore, KishuSession,
                        MemoryStore, Namespace, RecordBuilder)
from repro.core.chunkstore import (DirectoryStore, SQLiteStore, chunk_key,
                                   decode_chunk, encode_chunk, open_store,
                                   resolve_codec)
from repro.core import delta as delta_mod
from repro.core.checkpoint import WriteStats, build_manifest
from repro.core.checkout import materialize_manifest
from repro.core.covariable import cov_key
from repro.core.serialize import base_of


def make_store(kind, tmp_path):
    if kind == "memory":
        return MemoryStore()
    if kind == "dir":
        return DirectoryStore(str(tmp_path / "cas"))
    return SQLiteStore(str(tmp_path / "cas.db"))


@pytest.fixture(params=["memory", "dir", "sqlite"])
def store(request, tmp_path):
    return make_store(request.param, tmp_path)


CHUNK = 1 << 10                        # 1 KiB chunks
N = 2048                               # float32 -> 8 KiB -> 8 chunks


def _manifest_for(store, arr, prev=None, chunk=CHUNK):
    """Drive build_manifest directly for one single-member co-variable."""
    ns = Namespace({"x": arr})
    rb = RecordBuilder(chunk)
    rec = rb.build("x", arr, {})
    stats = WriteStats()
    man = build_manifest(store, ("x",), [rec], ns, chunk, prev, stats,
                         store.put_chunk)
    return man, stats


def _restored(store, man):
    return materialize_manifest(store, man)["x"]


# ---------------------------------------------------------------------------
# dirty-range serialization (det-hash reuse in build_manifest)
# ---------------------------------------------------------------------------

def test_build_manifest_unchanged_prev_serializes_nothing(store):
    arr = np.random.default_rng(0).standard_normal(N).astype(np.float32)
    man1, st1 = _manifest_for(store, arr)
    assert st1.bytes_serialized == st1.bytes_logical == arr.nbytes
    man2, st2 = _manifest_for(store, arr, prev=man1)
    assert st2.bytes_serialized == 0           # nothing moved
    assert st2.bytes_logical == arr.nbytes     # logical size still reported
    assert st2.chunks_reused == len(man1["base"]["chunks"])
    assert st2.covs_delta == 1
    assert man2["base"]["chunks"] == man1["base"]["chunks"]
    assert np.array_equal(_restored(store, man2), arr)


def test_build_manifest_partially_dirty_moves_only_dirty(store):
    rng = np.random.default_rng(1)
    arr = rng.standard_normal(N).astype(np.float32)
    man1, _ = _manifest_for(store, arr)
    arr2 = arr.copy()
    arr2[0] += 1.0                              # chunk 0
    arr2[-1] += 1.0                             # last chunk
    man2, st2 = _manifest_for(store, arr2, prev=man1)
    assert st2.bytes_serialized == 2 * CHUNK
    assert st2.bytes_logical == arr.nbytes
    assert st2.chunks_reused == 8 - 2
    # clean chunks reference the previous version's storage
    assert man2["base"]["chunks"][1:-1] == man1["base"]["chunks"][1:-1]
    assert np.array_equal(_restored(store, man2), arr2)


def test_build_manifest_meta_change_falls_back_to_full(store):
    arr = np.random.default_rng(2).standard_normal(N).astype(np.float32)
    man1, _ = _manifest_for(store, arr)
    arr2 = np.random.default_rng(3).standard_normal(N // 2).astype(np.float64)
    man2, st2 = _manifest_for(store, arr2, prev=man1)
    assert st2.covs_delta == 0                  # fast path not applicable
    assert st2.bytes_serialized == arr2.nbytes  # full serialization
    assert np.array_equal(_restored(store, man2), arr2)


def test_build_manifest_fully_dirty_takes_full_path(store):
    arr = np.random.default_rng(4).standard_normal(N).astype(np.float32)
    man1, _ = _manifest_for(store, arr)
    arr2 = arr + 1.0                            # every chunk dirty
    man2, st2 = _manifest_for(store, arr2, prev=man1)
    assert st2.covs_delta == 0
    assert st2.bytes_serialized == arr2.nbytes
    assert np.array_equal(_restored(store, man2), arr2)


def test_delta_chunks_bit_identical_to_full_path(store):
    """A chunk written through the dirty-range reader must hash and store
    exactly like one cut from the full blob."""
    rng = np.random.default_rng(5)
    arr = rng.standard_normal(N).astype(np.float32)
    man1, _ = _manifest_for(store, arr)
    arr2 = arr.copy()
    arr2[300] = 42.0
    man_delta, st = _manifest_for(store, arr2, prev=man1)
    assert st.covs_delta == 1
    man_full, _ = _manifest_for(MemoryStore(), arr2)   # no prev: full path
    assert [c["key"] for c in man_delta["base"]["chunks"]] \
        == [c["key"] for c in man_full["base"]["chunks"]]


def test_device_array_delta_write_and_patch(store):
    s = KishuSession(store, chunk_bytes=CHUNK, cache_bytes=0)

    def init(ns, seed):
        ns["w"] = jnp.arange(N, dtype=jnp.float32) * seed

    def bump(ns):
        ns["w"] = ns["w"].at[5].add(1.0)
    s.register("init", init)
    s.register("bump", bump)
    s.init_state({})
    c1 = s.run("init", seed=2)
    snap1 = np.asarray(s.ns["w"]).tobytes()
    c2 = s.run("bump")
    w = s.last_run.write
    assert w.covs_delta == 1
    assert w.bytes_serialized == CHUNK          # one dirty chunk transferred
    snap2 = np.asarray(s.ns["w"]).tobytes()
    st = s.checkout(c1)
    assert st.covs_patched == 1 and st.bytes_loaded == CHUNK
    assert isinstance(s.ns["w"], jax.Array)
    assert np.asarray(s.ns["w"]).tobytes() == snap1
    s.checkout(c2)
    assert np.asarray(s.ns["w"]).tobytes() == snap2
    s.close()


# ---------------------------------------------------------------------------
# in-place patch checkout
# ---------------------------------------------------------------------------

def _delta_session(store, cache_bytes=0):
    s = KishuSession(store, chunk_bytes=CHUNK, cache_bytes=cache_bytes)

    def init(ns, seed):
        rng = np.random.default_rng(seed)
        for i in range(3):
            ns[f"v{i}"] = rng.standard_normal(N).astype(np.float32)

    def mutate(ns, seed):
        rng = np.random.default_rng(seed)
        for i in range(3):
            ns[f"v{i}"][i] = rng.standard_normal()   # 1 dirty chunk per cov
    s.register("init", init)
    s.register("mutate", mutate)
    s.init_state({})
    return s


def _snap(s):
    return {n: np.asarray(s.ns[n]).tobytes() for n in s.ns.names()}


def test_patch_checkout_fetches_only_dirty_chunks(store):
    s = _delta_session(store)
    c1 = s.run("init", seed=1)
    snap1 = _snap(s)
    c2 = s.run("mutate", seed=9)
    snap2 = _snap(s)
    st = s.checkout(c1)
    assert st.covs_patched == 3
    assert st.chunks_patched == 3               # one dirty chunk per cov
    assert st.chunks_inplace == 3 * 8 - 3
    assert st.bytes_loaded == 3 * CHUNK         # moved ~ dirty, not logical
    assert st.bytes_logical == 3 * N * 4
    assert _snap(s) == snap1
    st = s.checkout(c2)                         # and forward again
    assert st.covs_patched == 3
    assert _snap(s) == snap2
    s.close()


def test_patch_preserves_live_object_identity(store):
    s = _delta_session(store)
    c1 = s.run("init", seed=1)
    c2 = s.run("mutate", seed=9)
    obj = s.ns["v0"]
    s.checkout(c1)
    assert s.ns["v0"] is obj                    # patched in place, not swapped
    s.close()


def test_patch_disabled_matches_patched_restore(store):
    """A co-variable whose dtype and shape changed since the target takes
    the full load by itself; it restores exactly beside a patched one."""
    s = _delta_session(store)

    def retype(ns):
        ns["v0"] = np.ones(N // 2, np.float64)   # same bytes, new meta
        ns["v1"][0] += 1.0                       # 1 dirty chunk, in place
    s.register("retype", retype)
    c1 = s.run("init", seed=1)
    snap1 = _snap(s)
    s.run("retype")
    st = s.checkout(c1)
    assert st.covs_patched == 1                 # v1 only: v0 not patched
    assert st.bytes_loaded == N * 4 + CHUNK     # v0 in full, v1's chunk
    assert _snap(s) == snap1
    s.close()


@pytest.mark.parametrize("codec", ["raw", "auto"])
def test_tenth_dirty_moves_at_most_a_fifth_of_the_bytes(store, codec):
    """With about a tenth of every co-variable's chunks dirty, a commit
    serializes and a checkout fetches at most a fifth of the logical
    bytes, and every restore is bit-identical."""
    if codec != "raw":
        store = CompressedStore(store, codec)
    n_chunks, chunk_elems = 16, 1024             # 4 KiB chunks
    s = KishuSession(store, chunk_bytes=4 * chunk_elems, cache_bytes=0)

    def init(ns, seed):
        rng = np.random.default_rng(seed)
        for i in range(2):
            ns[f"v{i}"] = rng.standard_normal(
                n_chunks * chunk_elems).astype(np.float32)

    def mutate(ns, seed):
        rng = np.random.default_rng(seed)
        for i in range(2):
            for c in range(2):                   # 2 of 16 chunks dirty
                ns[f"v{i}"][c * chunk_elems] = rng.standard_normal()
    s.register("init", init)
    s.register("mutate", mutate)
    s.init_state({})
    prev = s.run("init", seed=1)
    prev_snap = _snap(s)
    for r in range(2):
        cur = s.run("mutate", seed=100 + r)
        w = s.last_run.write
        assert w.bytes_logical == 2 * n_chunks * chunk_elems * 4
        assert w.bytes_serialized * 5 <= w.bytes_logical
        cur_snap = _snap(s)
        st = s.checkout(prev)
        assert (st.bytes_loaded + st.bytes_cached) * 5 <= st.bytes_logical
        assert _snap(s) == prev_snap
        s.checkout(cur)
        assert _snap(s) == cur_snap
        prev, prev_snap = cur, cur_snap
    s.close()


def test_patch_exactness_cross_checked_with_block_diff(store):
    """After a patch checkout the live buffer must be *exactly* the target
    — verified chunk-by-chunk with the exact (hash-free) compare."""
    s = _delta_session(store)
    c1 = s.run("init", seed=1)
    ref = {n: np.asarray(s.ns[n]).copy() for n in s.ns.names()}
    s.run("mutate", seed=9)
    s.checkout(c1)
    for n, want in ref.items():
        assert delta_mod.exact_dirty_indices(s.ns[n], want, CHUNK) == []
    s.close()


def test_structure_change_falls_back_to_full_load(store):
    s = KishuSession(store, chunk_bytes=CHUNK, cache_bytes=0)

    def a(ns):
        ns["x"] = np.ones(N, np.float32)

    def b(ns):
        ns["x"] = np.ones(N // 2, np.float64) * 2
    s.register("a", a)
    s.register("b", b)
    s.init_state({})
    ca = s.run("a")
    s.run("b")
    st = s.checkout(ca)
    assert st.covs_patched == 0                 # meta diverged: full load
    assert np.array_equal(s.ns["x"], np.ones(N, np.float32))
    s.close()


# ---------------------------------------------------------------------------
# codec round-trips
# ---------------------------------------------------------------------------

def test_codec_roundtrip_all_backends(store):
    cs = CompressedStore(store, "zlib")
    data = (b"compressible " * 1000)[:8192]
    k = chunk_key(data)
    assert cs.put_chunk(k, data)
    assert cs.get_chunk(k) == data
    # physically smaller on disk, logically intact through any reader
    assert store.get_chunk(k) == data           # backend decodes frames
    assert cs.stored_put_bytes < cs.logical_put_bytes


def test_codec_mixed_store_stays_readable(store):
    """Chunks written raw (old store) and compressed (new writer) coexist;
    either reader sees logical bytes."""
    raw_data = b"written before compression existed" * 100
    k_raw = chunk_key(raw_data)
    store.put_chunk(k_raw, raw_data)            # uncompressed writer
    cs = CompressedStore(store, "zlib")
    comp_data = b"written by the compressed writer" * 100
    k_comp = chunk_key(comp_data)
    cs.put_chunk(k_comp, comp_data)
    for reader in (store, cs):
        assert reader.get_chunk(k_raw) == raw_data
        assert reader.get_chunks([k_raw, k_comp]) \
            == {k_raw: raw_data, k_comp: comp_data}


def test_incompressible_chunks_stored_raw():
    inner = MemoryStore()
    cs = CompressedStore(inner, "zlib")
    noise = np.random.default_rng(0).bytes(4096)
    k = chunk_key(noise)
    cs.put_chunk(k, noise)
    assert inner.chunks[k] == noise             # no frame, zero overhead
    assert cs.get_chunk(k) == noise


def test_encode_decode_frame_contract():
    codec = resolve_codec("zlib")
    data = b"abc" * 5000
    enc = encode_chunk(data, codec)
    assert enc != data and decode_chunk(enc) == data
    assert decode_chunk(data) == data           # unframed passthrough
    assert encode_chunk(data, None) == data


def test_magic_prefixed_user_data_survives(store):
    """Logical chunk bytes that *begin with the frame magic* must round-trip
    through every backend and through the compressed writer — they are
    escaped (or decode-tolerated), never misparsed as a frame."""
    from repro.core.chunkstore import CHUNK_MAGIC
    for tail in (b"", b"\x00" * 40, b"not a frame at all" * 10,
                 b"\x01" + (8).to_bytes(8, "little") + b"xxxxxxxx"):
        data = CHUNK_MAGIC + tail
        k = chunk_key(data)
        store.put_chunk(k, data)                # raw writer
        assert store.get_chunk(k) == data
        store.delete_chunk(k)
        cs = CompressedStore(store, "zlib")     # compressed writer (escape)
        cs.put_chunk(k, data)
        assert cs.get_chunk(k) == data
        assert store.get_chunk(k) == data
        store.delete_chunk(k)


def test_session_end_to_end_compressed(store):
    cs = CompressedStore(store, "zlib")
    s = _delta_session(cs)
    c1 = s.run("init", seed=1)
    snap1 = _snap(s)
    c2 = s.run("mutate", seed=7)
    snap2 = _snap(s)
    assert s.checkout(c1).covs_patched == 3
    assert _snap(s) == snap1
    s.checkout(c2)
    assert _snap(s) == snap2
    s.close()


def test_open_store_codec_uri(tmp_path):
    cs = open_store(f"sqlite://{tmp_path}/c.db?codec=zlib")
    assert isinstance(cs, CompressedStore)
    with pytest.raises(ValueError):
        open_store("memory://?codec=nope")


# ---------------------------------------------------------------------------
# shared chunk cache (writer <-> loader coherence)
# ---------------------------------------------------------------------------

def test_checkout_of_just_committed_state_never_touches_backend():
    inner = MemoryStore()
    # every backend read fails: only the shared cache can serve checkout
    dark = FaultInjectedStore(inner, fail_get=lambda k: True)
    s = _delta_session(dark, cache_bytes=64 << 20)
    c1 = s.run("init", seed=1)
    snap1 = _snap(s)
    s.run("mutate", seed=3)
    st = s.checkout(c1)
    assert st.bytes_loaded == 0                 # zero backend bytes
    assert st.bytes_cached > 0
    assert st.covs_recomputed == 0
    assert _snap(s) == snap1
    s.close()


def test_cache_lru_eviction_bounds_memory():
    from repro.core import ChunkCache
    c = ChunkCache(max_bytes=3000)
    c.put("a", b"x" * 1000)
    c.put("b", b"y" * 1000)
    c.put("c", b"z" * 1000)
    assert c.get("a") is not None               # refresh a
    c.put("d", b"w" * 1000)                     # evicts b (LRU)
    assert c.get("b") is None
    assert c.get("a") is not None and c.get("d") is not None
    assert c.bytes_used <= 3000
    c.put("huge", b"h" * 5000)                  # larger than capacity: skip
    assert c.get("huge") is None


def test_cache_disabled_session_hits_backend(store):
    s = _delta_session(store, cache_bytes=0)
    c1 = s.run("init", seed=1)
    s.run("mutate", seed=2)
    st = s.checkout(c1)
    assert st.bytes_cached == 0 and st.bytes_loaded > 0
    s.close()
