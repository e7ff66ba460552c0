"""Pallas chunk_hash kernel vs pure-jnp oracle vs NumPy spec.

Sweeps shapes x dtypes in interpret mode (CPU executes the kernel body);
agreement must be bit-exact — the kernel IS the hash definition on TPU.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest


from repro.core import hashing as H
from repro.kernels.chunk_hash import chunk_hash, chunk_hash_u64
from repro.kernels.chunk_hash.kernel import chunk_hash_pallas
from repro.kernels.chunk_hash.ref import chunk_hash_ref

pytestmark = pytest.mark.slow    # JAX jit-heavy; fast lane: -m "not slow"

CB = 1 << 12

DTYPES = [np.float32, np.float16, np.int8, np.int32, np.uint8, np.int16]
SHAPES = [(1,), (7,), (1024,), (4096,), (4097,), (128, 33), (3, 5, 17)]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_pallas_matches_ref_and_numpy(dtype, shape):
    rng = np.random.default_rng(hash((np.dtype(dtype).name, shape)) % 2**32)
    if np.issubdtype(dtype, np.floating):
        x = rng.standard_normal(shape).astype(dtype)
    else:
        x = rng.integers(0, 100, shape).astype(dtype)
    xj = jnp.asarray(x)
    got_pallas = chunk_hash_u64(xj, CB, backend="pallas", interpret=True)
    got_ref = chunk_hash_u64(xj, CB, backend="ref")
    want = H.chunk_hashes_np(np.ascontiguousarray(x).tobytes(), CB)
    assert np.array_equal(got_pallas, want)
    assert np.array_equal(got_ref, want)


def test_bfloat16():
    x = jax.random.normal(jax.random.key(0), (1000, 33), jnp.bfloat16)
    got = chunk_hash_u64(x, CB, backend="pallas", interpret=True)
    want = H.chunk_hashes_np(np.asarray(x).tobytes(), CB)
    assert np.array_equal(got, want)


SUBWORD = [jnp.bfloat16, np.float16, np.int16, np.uint16, np.int8, np.uint8]
# NaN payloads, +-0, +-inf, denormals, all-ones (bf16 / fp16 bit patterns)
EDGE_BITS = {2: [0x7FC1, 0xFFC3, 0x7E01, 0x0000, 0x8000, 0x7F80, 0xFF80,
                 0x7C00, 0xFC00, 0x7E00, 0x0001, 0x8001, 0x03FF, 0xFFFF],
             1: [0x00, 0x80, 0x7F, 0x01, 0xFF]}


@pytest.mark.parametrize("dtype", SUBWORD)
@pytest.mark.parametrize("shape", [(1,), (255,), (256,), (257,), (511,),
                                   (4097,), (3, 1000)])
def test_to_words_subword_matches_host_bytes(dtype, shape):
    """16- and 8-bit items packed into words equal the host bytes, zero
    padded to a whole word, viewed as little-endian uint32."""
    from repro.kernels.chunk_hash.ops import _to_words

    item = np.dtype(dtype).itemsize
    ubits = np.uint16 if item == 2 else np.uint8
    rng = np.random.default_rng(item * 1000 + len(shape) * 100 + shape[-1])
    bits = rng.integers(0, 1 << (8 * item), shape).astype(ubits)
    flat = bits.reshape(-1)
    edge = np.asarray(EDGE_BITS[item], ubits)
    flat[:edge.size] = edge[:flat.size]
    x = bits.view(np.dtype(dtype))
    raw = x.tobytes()
    want = np.frombuffer(raw + b"\0" * (-len(raw) % 4), "<u4")
    got = np.asarray(jax.jit(_to_words)(jnp.asarray(x)))
    assert got.dtype == np.uint32
    assert np.array_equal(got, want)


def test_kernel_direct_prechunked():
    words = jnp.asarray(
        np.random.default_rng(0).integers(0, 2**32, (8, 1024), dtype=np.uint32))
    nbytes = jnp.full((8,), 4096, jnp.int32)
    k = chunk_hash_pallas(words, nbytes, interpret=True)
    r = chunk_hash_ref(words, nbytes)
    assert np.array_equal(np.asarray(k), np.asarray(r))


def test_chunk_sensitivity_on_device():
    x = jnp.zeros(CB * 4, jnp.uint8)                # 4 chunks
    h0 = chunk_hash_u64(x, CB, backend="pallas", interpret=True)
    x1 = x.at[CB + 5].set(1)                        # dirty chunk 1 only
    h1 = chunk_hash_u64(x1, CB, backend="pallas", interpret=True)
    assert h0[1] != h1[1]
    assert h0[0] == h1[0] and h0[2] == h1[2] and h0[3] == h1[3]


def test_vmem_block_is_power_of_two():
    with pytest.raises(AssertionError):
        chunk_hash(jnp.zeros(10, jnp.float32), 3 * 1024)
