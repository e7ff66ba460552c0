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
from repro.kernels.common import LANES
from repro.kernels.delta_codec.host import pow2ceil


def _lane_selector(per_word: int) -> np.ndarray:
    """0/1 [L, L] matrix, L = 128 * per_word, whose column k * 128 + j
    picks item per_word * j + k of a row: it moves the k-th item of every
    word into the k-th block of 128 lanes."""
    n = LANES * per_word
    src = np.arange(n)
    sel = np.zeros((n, n), np.float32)
    sel[src, (src % per_word) * LANES + src // per_word] = 1
    return sel


def _to_words(x: jax.Array) -> jax.Array:
    """Flatten + bitcast any-dtype array to uint32 words (little-endian).

    Narrow items are packed in lane-dense rows of 128 words: each byte of
    an item is a bf16 plane (0..255, exact), one 0/1 selection matmul
    (exact: one 1.0 x byte product per output) moves the k-th item of every
    word into the k-th block of 128 lanes, and shifts OR the blocks into
    words.  Strided slices of the flat array compile to gathers on a TPU,
    and an [N, 2] or [N, 4] intermediate is padded to 128 lanes (64x its
    size).  On a TPU, XLA's bitcast of bf16 / f16 data itself flushes
    denormals to zero and rewrites NaNs, so those items' bits are not kept
    there."""
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
                                     else jnp.uint8).astype(jnp.uint32)
    n = u.shape[0]
    row = LANES * per_word
    u = jnp.pad(u, (0, -n % row)).reshape(-1, row)
    sel = jnp.asarray(_lane_selector(per_word), jnp.bfloat16)
    words = jnp.zeros((u.shape[0], LANES), jnp.uint32)
    for b in range(item):
        plane = ((u >> (8 * b)) & 0xFF).astype(jnp.bfloat16)
        lanes = jnp.dot(plane, sel, preferred_element_type=jnp.float32
                        ).astype(jnp.uint32)
        for k in range(per_word):
            words = words | (lanes[:, k * LANES:(k + 1) * LANES]
                             << (8 * (item * k + b)))
    return words.reshape(-1)[:-(-n // per_word)]


@functools.lru_cache(maxsize=None)
def words_supported(dtype) -> bool:
    """Whether :func:`_to_words` can bitcast arrays of ``dtype`` (not bool,
    complex or sub-byte types) — checked on shapes only, nothing runs."""
    try:
        jax.eval_shape(_to_words, jax.ShapeDtypeStruct((1,), dtype))
    except (TypeError, ValueError):
        return False
    return True


@functools.partial(jax.jit, static_argnames=("chunk_bytes",))
def chunk_rows(x: jax.Array, chunk_bytes: int) -> jax.Array:
    """uint32 [n_chunks, chunk_bytes // 4] word rows of ``x``, the tail
    zero-padded (n_chunks >= 1): one compiled program per shape, dtype and
    chunk width, inlined where a jitted caller traces it."""
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
