"""jit'd public wrapper for on-device chunk hashing.

Handles arbitrary array dtypes/shapes: bitcasts to uint32 words (with
zero-padding), lays them out per chunk, dispatches to the Pallas kernel
(TPU; interpret mode in tests) or the jnp oracle, and packs the two 32-bit
lanes into uint64 detection hashes identical to
``repro.core.hashing.chunk_hashes_np``.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.kernels.chunk_hash.kernel import chunk_hash_pallas
from repro.kernels.chunk_hash.ref import chunk_hash_ref
from repro.kernels.delta_codec.host import pow2ceil


def _to_words(x: jax.Array) -> jax.Array:
    """Flatten + bitcast any-dtype array to uint32 words (little-endian).

    Narrow items are packed from strided slices of the flat array: an
    intermediate [N, 2] or [N, 4] array would be padded to 128 lanes in
    TPU memory (64x its size)."""
    flat = x.reshape(-1)
    item = np.dtype(x.dtype).itemsize
    if item == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if item == 8:
        w = jax.lax.bitcast_convert_type(flat, jnp.uint32)   # [..., 2]
        return w.reshape(-1)
    if item not in (1, 2):
        raise TypeError(f"unsupported itemsize {item} for dtype {x.dtype}")
    per_word = 4 // item
    u = jax.lax.bitcast_convert_type(flat, jnp.uint16 if item == 2
                                     else jnp.uint8)
    pad = (-u.shape[0]) % per_word
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad,), u.dtype)])
    words = u[0::per_word].astype(jnp.uint32)
    for k in range(1, per_word):
        words = words | (u[k::per_word].astype(jnp.uint32) << (8 * item * k))
    return words


@functools.lru_cache(maxsize=None)
def words_supported(dtype) -> bool:
    """Whether :func:`_to_words` can bitcast arrays of ``dtype`` (not bool,
    complex or sub-byte types) — checked on shapes only, nothing runs."""
    try:
        jax.eval_shape(_to_words, jax.ShapeDtypeStruct((1,), dtype))
    except (TypeError, ValueError):
        return False
    return True


def chunk_rows(x: jax.Array, chunk_bytes: int) -> jax.Array:
    """uint32 [n_chunks, chunk_bytes // 4] word rows of ``x``, the tail
    zero-padded (n_chunks >= 1)."""
    wpc = chunk_bytes // 4
    words = _to_words(x)
    n_chunks = max(-(-words.shape[0] // wpc), 1)
    pad = n_chunks * wpc - words.shape[0]
    if pad:
        words = jnp.concatenate([words, jnp.zeros((pad,), jnp.uint32)])
    return words.reshape(n_chunks, wpc)


def chunk_nbytes(nbytes_total: int, chunk_bytes: int) -> np.ndarray:
    """int32 [n_chunks] true byte count of every chunk (host math in int64:
    sizes can exceed int32)."""
    n_chunks = max(-(-int(nbytes_total) // chunk_bytes), 1)
    return np.minimum(
        np.full(n_chunks, chunk_bytes, np.int64),
        np.maximum(int(nbytes_total)
                   - np.arange(n_chunks, dtype=np.int64) * chunk_bytes, 0)
    ).astype(np.int32)


@functools.partial(jax.jit,
                   static_argnames=("chunk_bytes", "backend", "interpret"))
def chunk_hash(x: jax.Array, chunk_bytes: int = 1 << 18, *,
               backend: Literal["pallas", "ref"] = "pallas",
               interpret: bool = False) -> jax.Array:
    """Per-chunk detection hashes of an on-device array.

    Returns uint32 [n_chunks, 2].  ``chunk_bytes`` must be a power of two
    multiple of 4.
    """
    assert chunk_bytes % 4 == 0 and chunk_bytes & (chunk_bytes - 1) == 0
    nbytes_total = x.size * np.dtype(x.dtype).itemsize
    nbytes = jnp.asarray(chunk_nbytes(nbytes_total, chunk_bytes))
    # one chunk holding the whole array: hash a word row just wide enough
    # (padding words contribute nothing, so the hash is the same)
    width = chunk_bytes
    if nbytes_total < chunk_bytes:
        width = 4 * pow2ceil(max(1, -(-int(nbytes_total) // 4)))
    words = chunk_rows(x, width)
    if backend == "pallas":
        return chunk_hash_pallas(words, nbytes, interpret=interpret)
    return chunk_hash_ref(words, nbytes)


def chunk_hash_u64(x, chunk_bytes: int = 1 << 18, *,
                   backend: str = "pallas", interpret: bool = False
                   ) -> np.ndarray:
    """Host-side convenience: uint64 [n_chunks], matching chunk_hashes_np."""
    lanes = np.asarray(chunk_hash(x, chunk_bytes, backend=backend,
                                  interpret=interpret))
    return hashing.combine_u64(lanes)
