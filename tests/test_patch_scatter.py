"""Fused device-scatter checkout: kernel parity, dtype round-trips, the
patch_device_chunks contract, and end-to-end checkout bit-identity with
the scatter forced on (fast lane).

The invariant under test everywhere: scattering the dirty chunks of a
co-variable in ONE pass (kernels/patch_scatter, Pallas via interpret on
CPU) restores exactly the bytes the per-chunk ``dynamic_update_slice``
loop would have — on every supported dtype, alignment and tail shape.
An input the fused path does not take is refused up front (``None``); a
missing patch chunk demotes to a full load and is counted; a kernel error
propagates.
"""
import numpy as np
import pytest

from repro.core import delta as delta_mod
from repro.kernels.patch_scatter.ops import scatter_chunks

BACKENDS = ["ref", "pallas"]


def _scatter(x, idx, blobs, cb, backend):
    kw = {"interpret": True} if backend == "pallas" else {}
    return scatter_chunks(x, idx, blobs, cb, backend=backend, **kw)


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n,cb,dirty", [
    (4096, 256, [0]),
    (4096, 256, [0, 3, 15]),
    (4096, 256, list(range(16))),         # every chunk dirty
    (1000, 256, [1, 3]),                  # ragged tail chunk clean
    (1000, 256, [3]),                     # ragged tail chunk dirty
    (100, 256, [0]),                      # single short chunk
])
def test_scatter_matches_dus(backend, n, cb, dirty):
    import jax.numpy as jnp

    rng = np.random.default_rng(n + len(dirty))
    base_np = rng.integers(0, 2**31, n // 4, dtype=np.int64) \
        .astype(np.int32)
    base = jnp.asarray(base_np)
    blobs, segs = [], []
    for i in dirty:
        lo, hi = i * cb, min((i + 1) * cb, n)
        blob = rng.integers(0, 256, hi - lo, dtype=np.uint8).tobytes()
        blobs.append(blob)
        segs.append((lo, blob))
    got, moved = _scatter(base, dirty, blobs, cb, backend)
    want = delta_mod.patch_device_array(base, segs)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == base.dtype and got.shape == base.shape
    assert moved >= sum(len(b) for b in blobs)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", ["uint8", "int8", "uint16", "int16",
                                   "float16", "uint32", "int32", "float32"])
def test_scatter_roundtrip_dtypes(backend, dtype):
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    n = 777
    item = np.dtype(dtype).itemsize
    base_np = rng.integers(0, 250, n * item, dtype=np.uint8) \
        .view(dtype)[:n].copy()
    target_np = base_np.copy()
    cb = 64
    blobs, idx = [], []
    for i in (0, 3, (n * item - 1) // cb):
        lo, hi = i * cb, min((i + 1) * cb, n * item)
        blob = rng.integers(0, 250, hi - lo, dtype=np.uint8).tobytes()
        view = target_np.view(np.uint8)
        view[lo:hi] = np.frombuffer(blob, np.uint8)
        blobs.append(blob)
        idx.append(i)
    got, _ = _scatter(jnp.asarray(base_np), idx, blobs, cb, backend)
    assert np.asarray(got).tobytes() == target_np.tobytes()


@pytest.mark.parametrize("dtype", ["uint64", "int64", "float64"])
def test_scatter_roundtrip_wide_dtypes(dtype):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    n = 130
    item = np.dtype(dtype).itemsize
    base_np = rng.integers(0, 250, n * item, dtype=np.uint8) \
        .view(dtype)[:n].copy()
    target_np = base_np.copy()
    cb = 128
    blob = rng.integers(0, 250, cb, dtype=np.uint8).tobytes()
    target_np.view(np.uint8)[cb:2 * cb] = np.frombuffer(blob, np.uint8)
    with jax.enable_x64(True):
        got, _ = _scatter(jnp.asarray(base_np), [1], [blob], cb, "pallas")
        assert np.asarray(got).tobytes() == target_np.tobytes()
        assert got.dtype == base_np.dtype


@pytest.mark.parametrize("backend", BACKENDS)
def test_scatter_contract_violations(backend):
    import jax.numpy as jnp

    x = jnp.arange(1024, dtype=jnp.int32)
    blob = b"\0" * 256
    with pytest.raises(ValueError):
        _scatter(x, [99], [blob], 256, backend)      # index out of range
    with pytest.raises(ValueError):
        _scatter(x, [0], [blob], 255, backend)       # unaligned chunk size
    got, moved = _scatter(x, [], [], 256, backend)   # no-op
    assert moved == 0 and np.array_equal(np.asarray(got), np.asarray(x))


# --------------------------------------------- patch_device_chunks contract

def _covs(monkeypatch):
    monkeypatch.setenv("KISHU_DEVICE_SCATTER", "1")


def test_patch_device_chunks_applies(monkeypatch):
    import jax.numpy as jnp

    _covs(monkeypatch)
    base = jnp.asarray(np.arange(4096, dtype=np.int32))
    cb = 1024
    blob = (np.full(cb // 4, 9, np.int32)).tobytes()
    out = delta_mod.patch_device_chunks(base, [(cb, blob)], cb)
    assert out is not None
    patched, moved = out
    want = np.arange(4096, dtype=np.int32)
    want[cb // 4: 2 * cb // 4] = 9
    assert np.array_equal(np.asarray(patched), want)
    assert moved >= len(blob)


@pytest.mark.parametrize("case", ["env_off", "host_array", "unaligned_off",
                                  "short_seg", "bad_chunk_bytes", "bool",
                                  "complex"])
def test_patch_device_chunks_disengages(monkeypatch, case):
    import jax.numpy as jnp

    _covs(monkeypatch)
    cb = 1024
    base = jnp.asarray(np.arange(4096, dtype=np.int32))
    segs = [(cb, b"\x09" * cb)]
    if case == "env_off":
        monkeypatch.setenv("KISHU_DEVICE_SCATTER", "0")
    elif case == "host_array":
        base = np.arange(4096, dtype=np.int32)
    elif case == "unaligned_off":
        segs = [(cb + 4, b"\x09" * cb)]
    elif case == "short_seg":
        segs = [(cb, b"\x09" * (cb - 8))]
    elif case == "bad_chunk_bytes":
        cb = 1022
        segs = [(0, b"\x09" * cb)]
    elif case == "bool":
        base = jnp.asarray(np.ones(4096, bool))
        segs = [(cb, b"\x01" * cb)]
    elif case == "complex":
        # _to_words can't bitcast complex: the fused path must bow out
        base = jnp.asarray(np.zeros(1024, np.complex64))
        segs = [(cb, b"\x01" * cb)]
    assert delta_mod.patch_device_chunks(base, segs, cb) is None


def test_bool_and_complex128_fall_back_to_dus():
    """dtypes the word bitcast can't express still checkout correctly via
    the per-chunk DUS loop — the ladder degrades, never corrupts."""
    import jax.numpy as jnp

    base = jnp.asarray(np.zeros(4096, bool))
    blob = b"\x01" * 1024
    out = delta_mod.patch_device_array(base, [(1024, blob)])
    want = np.zeros(4096, bool)
    want[1024:2048] = True
    assert np.array_equal(np.asarray(out), want)


# ------------------------------------------------- end-to-end checkout path

def _mk_session(store, monkeypatch, scatter="1"):
    import jax.numpy as jnp

    from repro.core import KishuSession

    monkeypatch.setenv("KISHU_DEVICE_DELTA", "1")
    monkeypatch.setenv("KISHU_DEVICE_HASH", "1")
    monkeypatch.setenv("KISHU_DEVICE_CODEC", "1")
    monkeypatch.setenv("KISHU_DEVICE_SCATTER", scatter)
    sess = KishuSession(store, chunk_bytes=4096, cache_bytes=0)

    def init(ns):
        ns["v"] = jnp.arange(1 << 14, dtype=jnp.int32) % 89
        ns["w"] = jnp.arange(1 << 13, dtype=jnp.float32)

    def mutate(ns, seed):
        idx = jnp.arange(3) * 1024
        ns["v"] = ns["v"].at[idx].set(seed)
        ns["w"] = ns["w"].at[idx[:2]].set(float(seed))

    sess.register("init", init)
    sess.register("mutate", mutate)
    sess.init_state({})
    sess.run("init")
    return sess


def test_checkout_scatter_bit_identity(tmp_path, monkeypatch):
    """Same commits restored with the fused scatter forced on vs off must
    be byte-identical, and the scatter must cover every patched cov while
    accounting its host→device upload."""
    from repro.core import MemoryStore

    runs = {}
    for scatter in ("0", "1"):
        sess = _mk_session(MemoryStore(), monkeypatch, scatter=scatter)
        cids = [sess.run("mutate", seed=s) for s in (5, 6, 7)]
        states, scattered, h2d = [], 0, 0
        for cid in cids:
            st = sess.checkout(cid)
            scattered += st.covs_scattered
            h2d += st.bytes_host2dev
            assert st.covs_patched > 0
            states.append({n: np.asarray(sess.ns[n]).tobytes()
                           for n in sess.ns.names()})
        runs[scatter] = (states, scattered, h2d)
        if scatter == "1":
            assert scattered > 0 and h2d > 0
        else:
            assert scattered == 0
        sess.close()
    assert runs["0"][0] == runs["1"][0]


def test_fetch_patch_chunks_fallback_routes_through_counter(tmp_path,
                                                            monkeypatch):
    """A missing patch chunk must demote to a full-cov load *and* count as
    a kernel fallback (observable), not silently degrade."""
    from repro.core import MemoryStore

    store = MemoryStore()
    sess = _mk_session(store, monkeypatch)
    cid = sess.run("mutate", seed=3)
    sess.run("mutate", seed=4)

    # drop one chunk the patch planner will want for the checkout of `cid`
    man = sess.graph.nodes[cid].manifests
    victim = None
    for ks, m in man.items():
        for c in m["base"]["chunks"]:
            victim = c["key"]
            break
        break
    assert victim is not None
    del store.chunks[victim]

    fb0 = sess.obs.kernel_fallbacks()
    st = sess.checkout(cid)                  # must still restore (recompute
    assert sess.obs.kernel_fallbacks() > fb0  # or full load), and count
    assert st.kernel_fallbacks == sess.obs.kernel_fallbacks() - fb0
    want = np.arange(1 << 14, dtype=np.int32) % 89
    want[np.arange(3) * 1024] = 3
    assert np.array_equal(np.asarray(sess.ns["v"]), want)
    sess.close()


def test_checkout_scatter_kernel_error_propagates(monkeypatch):
    """A failing scatter is a device fault, not a corrupt patch: the
    checkout raises instead of reloading the co-variable, and counts no
    fallback."""
    from repro.core import MemoryStore
    from repro.kernels.patch_scatter import ops as scatter_ops

    sess = _mk_session(MemoryStore(), monkeypatch)
    cid = sess.run("mutate", seed=5)
    sess.run("mutate", seed=6)

    def boom(*a, **k):
        raise RuntimeError("scatter kernel failed")
    monkeypatch.setattr(scatter_ops, "scatter_chunks", boom)
    fb0 = sess.obs.kernel_fallbacks()
    with pytest.raises(RuntimeError, match="scatter kernel failed"):
        sess.checkout(cid)
    assert sess.obs.kernel_fallbacks() == fb0
    sess.close()
