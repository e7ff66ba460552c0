"""Fused delta_pack kernel + pipeline wiring tests (fast lane).

Covers the kernel contract (hashes / dirty vector / compacted buffer) on
both backends in interpret mode, segmenting, the env gate, the backend
choice by platform (and a kernel error propagating), and the end-to-end
guarantee:
a jax session on the fused path produces bit-identical checkpoints (same
states, same content-addressed chunk keys) as the host path.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import delta as delta_mod
from repro.core import hashing as H
from repro.kernels.delta_pack.ops import DeltaPack, delta_pack

BACKENDS = [("ref", {}), ("pallas", {"interpret": True})]


def _mk(nbytes, cb, dirty_chunks, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, nbytes, dtype=np.uint8)
    prev = H.chunk_hashes_np(a.tobytes(), cb)
    b = a.copy()
    for i in dirty_chunks:
        b[i * cb] ^= 0xFF
    return a, b, prev


@pytest.mark.parametrize("backend,kw", BACKENDS)
@pytest.mark.parametrize("nbytes,cb,dirty", [
    (4096 * 4, 1024, [0, 3, 7]),
    (4096 * 3 + 7, 1024, [0, 12]),       # odd tail, dirty last chunk region
    (17, 8, [1]),                        # sub-word tail
    (600, 1024, [0]),                    # single chunk, chunk_bytes > nbytes
])
def test_pack_contract(backend, kw, nbytes, cb, dirty):
    a, b, prev = _mk(nbytes, cb, dirty)
    pack = delta_pack(jnp.asarray(b), prev, cb, backend=backend, **kw)
    n_chunks = -(-nbytes // cb)
    assert pack.n_chunks == n_chunks and pack.nbytes == nbytes
    assert np.array_equal(pack.hashes,
                          H.chunk_hashes_np(b.tobytes(), cb))
    want_dirty = sorted(set(min(i, n_chunks - 1) for i in dirty))
    assert list(pack.dirty) == want_dirty
    got = dict(pack.read_chunks())
    assert sorted(got) == want_dirty
    for i, data in got.items():
        lo, hi = i * cb, min((i + 1) * cb, nbytes)
        assert data == b[lo:hi].tobytes()


@pytest.mark.parametrize("backend,kw", BACKENDS)
def test_pack_segmenting(backend, kw):
    """A tiny seg_bytes forces many pallas_call segments; compaction and
    chunk indexing must stay global across segment boundaries."""
    nbytes, cb = 64 * 256, 256           # 64 chunks
    dirty = [0, 1, 31, 32, 63]           # straddle every segment edge
    _, b, prev = _mk(nbytes, cb, dirty, seed=3)
    pack = delta_pack(jnp.asarray(b), prev, cb, backend=backend,
                      seg_bytes=4 * 256, **kw)     # 4 chunks per segment
    assert len(pack._segments) == 16
    assert list(pack.dirty) == dirty
    for i, data in pack.read_chunks():
        assert data == b[i * cb:(i + 1) * cb].tobytes()
    # partial reads hit only the owning segments
    sub = dict(pack.read_chunks([31, 63]))
    assert sorted(sub) == [31, 63]
    with pytest.raises(KeyError):
        list(pack.read_chunks([2]))      # clean chunk: not in the pack


def test_pack_transfer_accounting():
    nbytes, cb = 8 * 512, 512
    _, b, prev = _mk(nbytes, cb, [2], seed=5)
    pack = delta_pack(jnp.asarray(b), prev, cb, backend="ref")
    base = pack.bytes_transferred
    assert base == 8 * 12 + 4            # hash pairs + dirty flags + count
    list(pack.read_chunks())
    assert pack.bytes_transferred == base + cb   # one compacted row moved
    assert pack.bytes_transferred < nbytes       # never the whole array


def test_device_delta_pack_gating(monkeypatch):
    x = jnp.arange(1024, dtype=jnp.float32)
    prev = H.chunk_hashes_np(np.asarray(x).tobytes(), 1 << 10)
    monkeypatch.setenv("KISHU_DEVICE_DELTA", "0")
    assert delta_mod.device_delta_pack(x, prev, 1 << 10) is None
    monkeypatch.setenv("KISHU_DEVICE_DELTA", "1")
    pack = delta_mod.device_delta_pack(x, prev, 1 << 10)
    assert isinstance(pack, DeltaPack) and pack.count == 0
    # ladder guards: no prev hashes / wrong length / non-pow2 chunks / host
    assert delta_mod.device_delta_pack(x, None, 1 << 10) is None
    assert delta_mod.device_delta_pack(x, prev[:-1], 1 << 10) is None
    assert delta_mod.device_delta_pack(x, prev, 3000) is None
    assert delta_mod.device_delta_pack(np.arange(4), prev, 1 << 10) is None


def test_fallback_counter_and_log_once(monkeypatch, caplog):
    """A device kernel error propagates: exact_dirty_indices must neither
    swallow it into the host compare nor count it as a fallback (the
    counter is for storage and replay demotions only)."""
    import importlib
    import logging

    # repro.kernels re-exports the block_diff *function* over the submodule
    # name, so plain attribute-style import resolves to the function
    bd = importlib.import_module("repro.kernels.block_diff.ops")

    def boom(*a, **k):
        raise RuntimeError("kernel failed")
    monkeypatch.setattr(bd, "dirty_chunks", boom)
    a = jnp.arange(2048, dtype=jnp.float32)
    b = a.at[0].set(9.0)
    before = delta_mod.kernel_fallbacks()
    with caplog.at_level(logging.WARNING, logger="repro.core.delta"):
        with pytest.raises(RuntimeError, match="kernel failed"):
            delta_mod.exact_dirty_indices(a, b, 1 << 10)
    assert delta_mod.kernel_fallbacks() == before
    assert not [r for r in caplog.records if "device kernel" in r.message]
    # host arrays still take the NumPy compare
    assert delta_mod.exact_dirty_indices(np.asarray(a), np.asarray(b),
                                         1 << 10) == [0]


def _x_and_prev():
    x = jnp.arange(4096, dtype=jnp.float32)
    prev = H.chunk_hashes_np(np.asarray(x).tobytes(), 1 << 10)
    return x, prev


def _boom(*a, **k):
    raise RuntimeError("kernel failed")


# each device entry point: (call, module holding the Pallas function, name)
_ENTRY_POINTS = {
    "chunk_hashes_device": (
        lambda: H.chunk_hashes_device(_x_and_prev()[0], 1 << 10),
        "repro.kernels.chunk_hash.ops", "chunk_hash_pallas"),
    "device_delta_pack": (
        lambda: delta_mod.device_delta_pack(*_x_and_prev(), 1 << 10),
        "repro.kernels.delta_pack.kernel", "delta_pack_pallas"),
    "patch_device_chunks": (
        lambda: delta_mod.patch_device_chunks(
            _x_and_prev()[0], [(1024, b"\x07" * 1024)], 1 << 10),
        "repro.kernels.patch_scatter.kernel", "patch_scatter_pallas"),
    "exact_dirty_indices": (
        lambda: delta_mod.exact_dirty_indices(
            _x_and_prev()[0], _x_and_prev()[0].at[0].set(1.0), 1 << 10),
        "repro.kernels.block_diff.ops", "block_diff_pallas"),
}


def _force_device_paths(monkeypatch):
    for gate in ("KISHU_DEVICE_DELTA", "KISHU_DEVICE_HASH",
                 "KISHU_DEVICE_SCATTER"):
        monkeypatch.setenv(gate, "1")


@pytest.mark.parametrize("entry", sorted(_ENTRY_POINTS))
def test_platform_selects_reference_on_cpu(monkeypatch, entry):
    """Off a TPU the backend is the jnp reference, chosen by platform: the
    Pallas function is never called, and no fallback is counted."""
    import importlib

    from repro.kernels.common import platform_backend

    assert platform_backend() == "ref"
    assert platform_backend(jnp.zeros(4)) == "ref"
    call, module, name = _ENTRY_POINTS[entry]
    _force_device_paths(monkeypatch)
    monkeypatch.setattr(importlib.import_module(module), name, _boom)
    before = delta_mod.kernel_fallbacks()
    assert call() is not None
    assert delta_mod.kernel_fallbacks() == before


@pytest.mark.parametrize("entry", sorted(_ENTRY_POINTS))
def test_kernel_error_propagates(monkeypatch, entry):
    """Where the platform selects the Pallas kernel, its error reaches the
    caller: no try-the-next-backend ladder, no host path, no counter."""
    import importlib
    import sys

    call, module, name = _ENTRY_POINTS[entry]
    _force_device_paths(monkeypatch)
    call_module = importlib.import_module(module)
    for mod in [m for n, m in list(sys.modules.items())
                if n.startswith("repro.") and hasattr(m, "platform_backend")]:
        monkeypatch.setattr(mod, "platform_backend", lambda x=None: "pallas")
    monkeypatch.setattr(call_module, name, _boom)
    before = delta_mod.kernel_fallbacks()
    with pytest.raises(RuntimeError, match="kernel failed"):
        call()
    assert delta_mod.kernel_fallbacks() == before


def test_sharded_array_takes_host_path(monkeypatch):
    """An array spread over several devices is refused by an explicit
    check before any kernel runs."""
    from repro.core.delta import device_kernel_applies

    x, prev = _x_and_prev()
    assert device_kernel_applies(x)

    class _TwoDevices:
        device_set = {"d0", "d1"}

    class _Sharded:
        def __init__(self, arr):
            self._arr = arr

        def __getattr__(self, name):
            return getattr(self._arr, name)

        sharding = _TwoDevices()

    import jax
    monkeypatch.setattr(jax, "Array", (type(x), _Sharded))
    _force_device_paths(monkeypatch)
    sharded = _Sharded(x)
    assert not device_kernel_applies(sharded)
    assert delta_mod.device_delta_pack(sharded, prev, 1 << 10) is None
    assert H.chunk_hashes_device(sharded, 1 << 10) is None


def _session_states(store, force: str, chunk_bytes=1 << 12):
    from repro.core import KishuSession
    sess = KishuSession(store, chunk_bytes=chunk_bytes, cache_bytes=0)

    def init(ns):
        ns["x"] = jnp.arange(8192, dtype=jnp.float32)
        ns["y"] = jnp.zeros((2048,), jnp.int32)

    def mutate(ns, seed):
        ns["x"] = ns["x"].at[:1024].set(float(seed))
        ns["y"] = ns["y"] + seed

    sess.register("init", init)
    sess.register("mutate", mutate)
    sess.init_state({})
    cids = [sess.run("init")]
    cids += [sess.run("mutate", seed=s) for s in (3, 5)]
    wstats = sess.last_run.write
    states = []
    for cid in cids:
        sess.checkout(cid)
        states.append({n: np.asarray(sess.ns[n]).tobytes()
                       for n in sess.ns.names()})
    keys = sorted(store.list_chunk_keys())
    sess.close()
    return states, keys, wstats


def test_session_fused_vs_host_bit_identical(monkeypatch):
    """End to end: the fused device path commits the same chunk keys and
    restores the same bytes as the host path, and WriteStats records the
    pack usage and the device→host bytes of each: on the host path every
    byte serialized from a device array crossed."""
    from repro.core import MemoryStore
    monkeypatch.setenv("KISHU_DEVICE_DELTA", "1")
    monkeypatch.setenv("KISHU_DEVICE_HASH", "1")
    dev_states, dev_keys, dev_w = _session_states(MemoryStore(), "1")
    monkeypatch.setenv("KISHU_DEVICE_DELTA", "0")
    monkeypatch.setenv("KISHU_DEVICE_HASH", "0")
    host_states, host_keys, host_w = _session_states(MemoryStore(), "0")
    assert dev_states == host_states
    assert dev_keys == host_keys
    assert dev_w.covs_packed >= 1
    assert 0 < dev_w.bytes_dev2host < dev_w.bytes_logical
    assert host_w.covs_packed == 0
    assert host_w.bytes_dev2host == host_w.bytes_serialized > 0


def test_checkout_stats_have_fallback_counter():
    from repro.core.checkout import CheckoutStats
    assert CheckoutStats().kernel_fallbacks == 0
