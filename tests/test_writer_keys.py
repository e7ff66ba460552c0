"""The full serialization path keys a many-chunk leaf on the I/O pool, from
views of its host copy: the same manifests, chunk keys, stored bytes and
write stats as keying it serially, one private copy per new chunk, and the
``keys_s`` / ``kishu_chunks_keyed_total`` accounting of what was keyed
where."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import KishuSession, MemoryStore, Namespace, RecordBuilder
from repro.core import checkpoint, parallel
from repro.core.checkpoint import CheckpointWriter
from repro.core.chunkstore import ChunkCache, DirectoryStore, SQLiteStore

CHUNK = 4096


def _leaf(case):
    """A leaf whose every chunk differs from ``_leaf(case) + 1``'s."""
    rng = np.random.default_rng(7)
    if case == "bf16":
        return jnp.asarray(rng.standard_normal(24 * CHUNK // 2),
                           dtype=jnp.bfloat16)
    if case == "f32":
        return rng.standard_normal(24 * CHUNK // 4).astype(np.float32)
    if case == "int8":
        return rng.integers(-128, 127, 24 * CHUNK, dtype=np.int8)
    if case == "prng":                   # 4096 keys: 8 chunks of key data
        return jax.random.split(jax.random.key(3), 4096)
    if case == "ragged":                 # 20 chunks and 1,236 bytes
        return rng.standard_normal(20 * CHUNK // 4 + 309).astype(np.float32)
    if case == "repeated":               # 6 distinct chunks, 4 times over
        block = rng.standard_normal(6 * CHUNK // 4).astype(np.float32)
        return np.tile(block, 4)
    raise ValueError(case)


def _next(case, leaf):
    if case == "prng":
        return jax.random.split(jax.random.key(4), 4096)
    if case == "bf16":
        return leaf + jnp.asarray(1, jnp.bfloat16)
    return leaf + np.ones((), leaf.dtype)


def _labelled(o, name, label):
    return {dict(labels)[label]: c.value
            for (n, labels), c in o.registry._counters.items() if n == name}


def _write_history(store, case, async_write):
    """Three full-path commits of one leaf: a first version, a fully dirty
    one, and the first again, whose chunks all dedup against the store."""
    o = obs.SessionObs()
    writer = CheckpointWriter(store, chunk_bytes=CHUNK,
                              async_write=async_write,
                              cache=ChunkCache(1 << 24))
    v0 = _leaf(case)
    manifests, stats, prev = [], [], None
    for value in (v0, _next(case, v0), v0):
        ns = Namespace({"x": value})
        rec = RecordBuilder(CHUNK).build("x", value, {})
        delta = types.SimpleNamespace(updated={("x",): [rec]})
        with o.activate():
            mans, st = writer.write_delta(delta, ns, lambda key: prev)
        writer.flush()
        (prev,) = mans.values()
        manifests.append(mans)
        stats.append({k: v for k, v in dataclasses.asdict(st).items()
                      if k != "wall_s"})
    writer.close()
    stored = {k: store.get_chunk(k) for k in store.list_chunk_keys()}
    return manifests, stats, stored, _labelled(
        o, "kishu_chunks_keyed_total", "mode")


@pytest.mark.parametrize("async_write", [False, True],
                         ids=["sync", "async"])
@pytest.mark.parametrize("backend", ["dir", "sqlite"])
@pytest.mark.parametrize("case", ["bf16", "f32", "int8", "prng", "ragged",
                                  "repeated"])
def test_pooled_keys_identical_to_serial(tmp_path, monkeypatch, case,
                                         backend, async_write):
    def store(tag):
        if backend == "dir":
            return DirectoryStore(str(tmp_path / tag))
        return SQLiteStore(str(tmp_path / f"{tag}.db"))

    monkeypatch.setenv("KISHU_IO_THREADS", "4")
    monkeypatch.setattr(checkpoint, "KEY_POOL_MIN_CHUNKS", 2)
    pooled = _write_history(store("pool"), case, async_write)
    monkeypatch.setenv("KISHU_IO_THREADS", "1")
    serial = _write_history(store("serial"), case, async_write)

    for got, want in zip(pooled[:3], serial[:3]):
        assert got == want              # manifests, stats, stored chunks
    keyed = sum(s["chunks_written"] + s["chunks_dedup"] for s in pooled[1])
    assert pooled[3] == {"pool": keyed}
    assert serial[3] == {"serial": keyed}
    first, _, again = pooled[1]
    assert again["chunks_written"] == 0 < again["chunks_dedup"]
    if case == "repeated":              # within-leaf dedup on the first
        assert first["chunks_dedup"] == 18 and first["chunks_written"] == 6


@pytest.mark.parametrize("async_write", [False, True],
                         ids=["sync", "async"])
def test_a_host_leaf_mutated_after_commit_reads_back_as_committed(
        tmp_path, monkeypatch, async_write):
    monkeypatch.setenv("KISHU_IO_THREADS", "4")
    monkeypatch.setattr(checkpoint, "KEY_POOL_MIN_CHUNKS", 2)
    store = DirectoryStore(str(tmp_path / "cas"))
    cache = ChunkCache(1 << 24)
    writer = CheckpointWriter(store, chunk_bytes=CHUNK,
                              async_write=async_write, cache=cache)
    arr = np.random.default_rng(1).standard_normal(
        16 * CHUNK // 4).astype(np.float32)
    committed = arr.tobytes()
    ns = Namespace({"x": arr})
    delta = types.SimpleNamespace(
        updated={("x",): [RecordBuilder(CHUNK).build("x", arr, {})]})
    mans, _ = writer.write_delta(delta, ns, lambda key: None)
    arr[:] = -1.0                       # before an async drain has landed
    writer.flush()
    writer.close()
    (man,) = mans.values()
    chunks = man["base"]["chunks"]
    assert b"".join(cache.get(c["key"]) for c in chunks) == committed
    assert b"".join(store.get_chunk(c["key"]) for c in chunks) == committed


def _session(monkeypatch, **kw):
    monkeypatch.setenv("KISHU_IO_THREADS", "4")
    sess = KishuSession(MemoryStore(), chunk_bytes=CHUNK, cache_bytes=0,
                        **kw)

    def fill(ns, scale):
        ns["big"] = np.full(64 * CHUNK // 4, scale, np.float32) \
            + np.arange(64 * CHUNK // 4, dtype=np.float32)
        ns["small"] = ("python", scale)
    sess.register("fill", fill)
    sess.init_state({})
    return sess


def test_keyed_counter_pool_for_a_big_leaf_serial_for_a_python_value(
        monkeypatch):
    sess = _session(monkeypatch)
    sess.run("fill", scale=1.0)
    keyed = _labelled(sess.obs, "kishu_chunks_keyed_total", "mode")
    assert keyed == {"pool": 64, "serial": 1}
    assert 64 >= checkpoint.KEY_POOL_MIN_CHUNKS
    sess.close()


def test_keying_is_serial_inside_a_serial_section(monkeypatch):
    sess = _session(monkeypatch)
    calls = []
    real = parallel.map_parallel
    monkeypatch.setattr(parallel, "map_parallel",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    with parallel.serial_section():
        sess.run("fill", scale=1.0)
    assert _labelled(sess.obs, "kishu_chunks_keyed_total", "mode") == {
        "serial": 65}
    assert not calls
    sess.close()


@pytest.mark.parametrize("io_threads", ["1", "4"])
def test_traced_keys_s_is_wall_time_inside_the_manifest_span(
        monkeypatch, io_threads):
    sess = _session(monkeypatch, trace=True)
    monkeypatch.setenv("KISHU_IO_THREADS", io_threads)
    sess.run("fill", scale=1.0)
    sess.run("fill", scale=2.0)          # fully dirty: the full path again
    spans = [s for s in sess.obs.tracer.spans if s.name == "manifest"]
    big = [s for s in spans if s.args["chunks"] == 64]
    assert len(big) == 2
    for s in spans:
        assert 0.0 <= s.args["keys_s"] <= s.dur_s
        assert s.args["keyed_pool"] in (0, s.args["chunks"])
    assert all(s.args["keyed_pool"] == (64 if io_threads == "4" else 0)
               and s.args["keys_s"] > 0 for s in big)
    sess.close()
