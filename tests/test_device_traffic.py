"""The device paths against the host path, end to end (fast lane).

A session of compressible int32 device arrays, about a tenth (or half) of
whose chunks are rewritten in place per cell, runs once with every device
path off (the host path) and once with the fused delta pack on (``delta``)
or with the on-device codec and the fused scatter on as well (``codec``).
Each device run must restore the host run's states bit for bit and leave
the same content-addressed chunk keys in the store, and its device→host
traffic must stay a small share of the logical bytes it commits.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KishuSession, MemoryStore
from repro.core.chunkstore import SQLiteStore

_ENV_KEYS = ("KISHU_DEVICE_DELTA", "KISHU_DEVICE_HASH",
             "KISHU_DEVICE_CODEC", "KISHU_DEVICE_SCATTER")
_ENV = {"host": "0000", "delta": "1100", "codec": "1111"}

ELEMS, CHUNK = 1 << 14, 1 << 12             # 16 chunks of 4 KiB per array


def _run(store, mode, dirty_frac, monkeypatch):
    for k, v in zip(_ENV_KEYS, _ENV[mode]):
        monkeypatch.setenv(k, v)
    n_chunks = ELEMS * 4 // CHUNK
    touch = jnp.arange(max(1, round(n_chunks * dirty_frac))) * (CHUNK // 4)
    sess = KishuSession(store, chunk_bytes=CHUNK, cache_bytes=0)

    def init(ns, seed):
        for i in range(2):                  # values < 2**7: compressible
            ns[f"v{i}"] = jnp.arange(ELEMS, dtype=jnp.int32) * (seed + i) % 97

    def mutate(ns, seed):
        for i in range(2):
            ns[f"v{i}"] = ns[f"v{i}"].at[touch].set(seed % 89 + i)
    sess.register("init", init)
    sess.register("mutate", mutate)
    sess.init_state({})
    sess.run("init", seed=1)
    cids, writes = [], []
    for r in range(2):
        cids.append(sess.run("mutate", seed=100 + r))
        writes.append(sess.last_run.write)
    states, checkouts = [], []
    for cid in cids:
        checkouts.append(sess.checkout(cid))
        states.append({n: np.asarray(sess.ns[n]).tobytes()
                       for n in sess.ns.names()})
    keys = sorted(store.list_chunk_keys())
    sess.close()
    return states, keys, writes, checkouts


def _store(backend, tmp_path, tag):
    if backend == "memory":
        return MemoryStore()
    return SQLiteStore(str(tmp_path / f"{tag}.db"))


@pytest.mark.parametrize("mode,backend,dirty_frac,max_d2h", [
    ("delta", "memory", 0.01, 0.15),
    ("delta", "memory", 0.10, 0.15),
    ("delta", "sqlite", 0.10, 0.15),
    ("codec", "memory", 0.10, 0.05),
    ("codec", "sqlite", 0.10, 0.05),
    ("codec", "memory", 0.50, None),
])
def test_device_path_matches_host_path(mode, backend, dirty_frac, max_d2h,
                                       tmp_path, monkeypatch):
    host_states, host_keys, _, _ = _run(
        _store(backend, tmp_path, "host"), "host", dirty_frac, monkeypatch)
    states, keys, writes, checkouts = _run(
        _store(backend, tmp_path, mode), mode, dirty_frac, monkeypatch)
    assert states == host_states
    assert keys == host_keys
    assert all(w.covs_packed > 0 for w in writes)
    if max_d2h is not None:
        d2h = sum(w.bytes_dev2host for w in writes)
        logical = sum(w.bytes_logical for w in writes)
        assert 0 < d2h <= max_d2h * logical
    if mode == "codec":
        assert all(w.chunks_encoded > 0 for w in writes)
        for st in checkouts:
            assert st.covs_patched > 0
            assert st.covs_scattered == st.covs_patched
            assert st.bytes_host2dev > 0
