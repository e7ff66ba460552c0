"""Chunk-hash specification — the VarGraph node-compare, TPU-adapted.

One hash definition, three interchangeable implementations that MUST agree
bit-for-bit (tested):

  - :func:`chunk_hashes_np`   — vectorized NumPy (host path; used by the
                                 session on CPU arrays)
  - :func:`chunk_hashes_jnp`  — pure jnp (oracle for the Pallas kernel)
  - ``repro.kernels.chunk_hash`` — Pallas TPU kernel (HBM-bandwidth path)

Design: an order-sensitive, embarrassingly-parallel 2x32-bit hash.  Each
uint32 word is avalanche-mixed with its position, lanes are XOR-reduced, and
the chunk byte-length is folded in (so zero-padding cannot collide with real
zeros of a different length).  XOR-reduction makes the hash a pure map-reduce:
ideal for the VPU (no sequential dependency, unlike FNV).

Detection-grade hashing: equality of the 64-bit pair is treated as
"unchanged" (false-equal probability ~2^-64 per chunk — the same accuracy
class as the paper's pickling assumption, DESIGN.md §2).  *Storage* keys use
blake2b (exact) in the chunk store; this hash only decides what to inspect.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

GOLDEN = np.uint32(0x9E3779B9)
C1 = np.uint32(0x85EBCA6B)
C2 = np.uint32(0xC2B2AE35)
SEEDS = (np.uint32(0), np.uint32(0x517CC1B7))
DEFAULT_CHUNK_BYTES = 1 << 20


def _mix_np(w: np.ndarray, idx: np.ndarray, seed: np.uint32,
            n_valid: np.ndarray) -> np.ndarray:
    """Avalanche-mix words with their position; words past ``n_valid`` (zero
    padding) contribute 0, so the hash is independent of padding length."""
    with np.errstate(over="ignore"):
        m = (w ^ (idx * GOLDEN + seed)) * C1
        m ^= m >> np.uint32(16)
        m = m * C2
        m ^= m >> np.uint32(13)
    return np.where(idx < n_valid, m, np.uint32(0))


def _finalize_np(h: np.ndarray, nbytes: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = (h ^ nbytes.astype(np.uint32)) * C1
        h ^= h >> np.uint32(16)
    return h


def _effective_chunk_bytes(n: int, chunk_bytes: int) -> int:
    """Clamp the chunk size to the buffer length (word-aligned) so a huge
    configured chunk size (whole-co-variable mode) never allocates a huge
    zero pad.  Hash equality only ever compares same-length buffers, so the
    clamp is consistent across versions."""
    if chunk_bytes >= n:
        return max(((n + 3) // 4) * 4, 4)
    return chunk_bytes


def chunk_hashes_np(buf: bytes | np.ndarray,
                    chunk_bytes: int = DEFAULT_CHUNK_BYTES) -> np.ndarray:
    """Per-chunk 64-bit hashes of a byte buffer. Returns uint64 [n_chunks]."""
    raw = np.frombuffer(buf, dtype=np.uint8) if isinstance(buf, (bytes, bytearray, memoryview)) \
        else np.ascontiguousarray(buf).reshape(-1).view(np.uint8)
    n = raw.size
    if n == 0:
        return np.zeros((0,), np.uint64)
    assert chunk_bytes % 4 == 0
    chunk_bytes = _effective_chunk_bytes(n, chunk_bytes)
    n_chunks = -(-n // chunk_bytes)
    padded = np.zeros(n_chunks * chunk_bytes, np.uint8)
    padded[:n] = raw
    words = padded.view(np.uint32).reshape(n_chunks, chunk_bytes // 4)
    idx = np.arange(chunk_bytes // 4, dtype=np.uint32)[None, :]
    nbytes = np.minimum(
        np.full(n_chunks, chunk_bytes, np.int64),
        n - np.arange(n_chunks, dtype=np.int64) * chunk_bytes)
    n_valid = ((nbytes + 3) // 4).astype(np.uint32)[:, None]
    lanes = []
    for seed in SEEDS:
        m = _mix_np(words, idx, seed, n_valid)
        h = np.bitwise_xor.reduce(m, axis=1)
        lanes.append(_finalize_np(h, nbytes))
    return (lanes[0].astype(np.uint64) << np.uint64(32)) | lanes[1].astype(np.uint64)


def chunk_hashes_jnp(words, nbytes):
    """jnp oracle over pre-chunked words.

    words: uint32 [n_chunks, words_per_chunk]; nbytes: int32 [n_chunks]
    (true byte count per chunk).  Returns uint32 [n_chunks, 2].
    """
    import jax
    import jax.numpy as jnp
    idx = jnp.arange(words.shape[1], dtype=jnp.uint32)[None, :]
    n_valid = ((nbytes.astype(jnp.uint32) + 3) // 4)[:, None]
    outs = []
    for seed in SEEDS:
        m = (words ^ (idx * jnp.uint32(GOLDEN) + jnp.uint32(seed))) * jnp.uint32(C1)
        m = m ^ (m >> 16)
        m = m * jnp.uint32(C2)
        m = m ^ (m >> 13)
        m = jnp.where(idx < n_valid, m, jnp.uint32(0))
        h = jax.lax.reduce(m, jnp.uint32(0), jax.lax.bitwise_xor, (1,))
        h = (h ^ nbytes.astype(jnp.uint32)) * jnp.uint32(C1)
        h = h ^ (h >> 16)
        outs.append(h)
    return jnp.stack(outs, axis=-1)


def hashes_hex(h) -> list:
    """uint64 [n] -> 16-char hex strings (manifest / record interchange)."""
    if h is None:
        return []
    return [format(int(x), "016x") for x in np.asarray(h, dtype=np.uint64)]


def chunk_hashes_device(x, chunk_bytes: int = DEFAULT_CHUNK_BYTES
                        ) -> Optional[np.ndarray]:
    """Detection hashes of a *device* array without a host round-trip.

    Runs the ``chunk_hash`` kernel (the Pallas kernel on a TPU, the jnp
    oracle elsewhere).  Returns ``None`` when the input is not for it — a
    non-power-of-two chunk, or not a one-device jax array of a
    word-bitcastable dtype (``delta.device_kernel_applies``) — and the
    caller hashes on host via :func:`chunk_hashes_np`.  A kernel error
    raises.  Only engaged off-CPU by default — on CPU the NumPy path is
    faster than jit dispatch — override with ``KISHU_DEVICE_HASH=1/0``.
    Bit-identical to ``chunk_hashes_np`` by the kernel contract (tested).
    """
    if chunk_bytes % 4 or chunk_bytes & (chunk_bytes - 1):
        return None                 # kernel wants a power-of-two chunk
    env = os.environ.get("KISHU_DEVICE_HASH", "").strip()
    if env == "0":
        return None
    import jax
    if env != "1" and jax.default_backend() == "cpu":
        return None
    from repro.core.delta import device_kernel_applies, note_kernel_call
    if not device_kernel_applies(x):
        return None
    from repro.kernels.chunk_hash.ops import chunk_hash_u64
    from repro.kernels.common import platform_backend
    backend = platform_backend(x)
    note_kernel_call("chunk_hash", backend)
    return chunk_hash_u64(x, chunk_bytes, backend=backend)


def combine_u64(lanes) -> np.ndarray:
    """uint32 [n,2] -> uint64 [n] (matches chunk_hashes_np packing)."""
    lanes = np.asarray(lanes)
    return (lanes[:, 0].astype(np.uint64) << np.uint64(32)) \
        | lanes[:, 1].astype(np.uint64)


def split_u64(h) -> np.ndarray:
    """uint64 [n] -> uint32 [n,2] lanes (inverse of :func:`combine_u64`) —
    the previous-hash operand of the fused ``delta_pack`` kernel."""
    h = np.asarray(h, dtype=np.uint64)
    return np.stack([(h >> np.uint64(32)).astype(np.uint32),
                     (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)], axis=1)


def words_view(buf: bytes | np.ndarray, chunk_bytes: int):
    """Pre-chunk a buffer for the jnp/pallas paths.

    Returns (words uint32 [n_chunks, W], nbytes int32 [n_chunks]).
    """
    raw = np.frombuffer(buf, dtype=np.uint8) if isinstance(buf, (bytes, bytearray, memoryview)) \
        else np.ascontiguousarray(buf).reshape(-1).view(np.uint8)
    n = raw.size
    chunk_bytes = _effective_chunk_bytes(max(n, 1), chunk_bytes)
    n_chunks = max(-(-n // chunk_bytes), 1)
    padded = np.zeros(n_chunks * chunk_bytes, np.uint8)
    padded[:n] = raw
    words = padded.view(np.uint32).reshape(n_chunks, chunk_bytes // 4)
    nbytes = np.minimum(
        np.full(n_chunks, chunk_bytes, np.int64),
        np.maximum(n - np.arange(n_chunks, dtype=np.int64) * chunk_bytes, 0))
    return words, nbytes.astype(np.int32)
