"""bytes-in wrappers for the fused device scatter.

``scatter_chunks`` takes the live device array, the dirty chunk indices and
their raw chunk bytes (as fetched from the store, already decoded to
logical bytes), uploads ONE compacted [K, W] uint32 buffer + index vector,
and lands every chunk in a single kernel pass.  The inverse bitcasts
(``_from_words``) mirror ``chunk_hash.ops._to_words`` exactly, so the
round-trip is bit-identical for every supported dtype.

K varies per checkout, so rows/idx are padded to the next power of two by
repeating row 0 (idempotent duplicate writes) — the jit cache stays
O(log max_rows) per (C, W).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from repro.kernels.delta_codec.host import pow2ceil


def _from_words(words, dtype, shape):
    """Inverse of ``chunk_hash.ops._to_words``: uint32 device words back to
    an array of ``dtype``/``shape`` (little-endian lane order)."""
    import jax
    import jax.numpy as jnp

    dt = np.dtype(dtype)
    n = 1
    for s in shape:
        n *= int(s)
    item = dt.itemsize
    nw = -(-n * item // 4)
    w = words[:nw]
    if dt.kind == "c":
        if item != 8:
            raise TypeError(f"unsupported complex itemsize {item}")
        f = jax.lax.bitcast_convert_type(w, jnp.float32)
        out = jax.lax.complex(f[0::2], f[1::2])
    elif item == 4:
        out = jax.lax.bitcast_convert_type(w, dt)
    elif item == 8:
        out = jax.lax.bitcast_convert_type(w.reshape(-1, 2), dt)
    elif item == 2:
        u = jax.lax.bitcast_convert_type(w, jnp.uint16).reshape(-1)[:n]
        out = jax.lax.bitcast_convert_type(u, dt) \
            if dt != np.dtype(np.uint16) else u
    elif item == 1:
        u = jax.lax.bitcast_convert_type(w, jnp.uint8).reshape(-1)[:n]
        if dt == np.dtype(bool):
            out = u.astype(jnp.bool_)
        elif dt == np.dtype(np.uint8):
            out = u
        else:
            out = jax.lax.bitcast_convert_type(u, dt)
    else:
        raise TypeError(f"unsupported itemsize {item} for dtype {dt}")
    return out.reshape(shape)


def _rows_from_blobs(blobs: Sequence[bytes], width: int) -> np.ndarray:
    """Pack per-chunk logical bytes into a [K, width] uint32 row buffer
    (zero-padded tail — pad bits land past raw_len and are dropped by
    ``_from_words``'s element slice)."""
    rows = np.zeros((len(blobs), width * 4), np.uint8)
    for r, blob in enumerate(blobs):
        b = np.frombuffer(blob, np.uint8)
        rows[r, :b.size] = b
    return rows.view("<u4").reshape(len(blobs), width)


def scatter_chunks(x, idx: Sequence[int], blobs: Sequence[bytes],
                   chunk_bytes: int, *, backend: str = "pallas",
                   interpret: bool = False) -> Tuple[object, int]:
    """Patch chunks ``idx`` of device array ``x`` with ``blobs`` in one
    fused pass.

    Returns (patched array, bytes moved host->device).  Raises on any
    contract violation; ``delta.patch_device_chunks`` checks the contract
    before it calls."""
    import jax.numpy as jnp

    from repro.kernels.chunk_hash.ops import chunk_rows

    if chunk_bytes <= 0 or chunk_bytes % 4:
        raise ValueError(f"chunk_bytes {chunk_bytes} not word-aligned")
    if not blobs:
        return x, 0
    width = chunk_bytes // 4
    words = chunk_rows(x, chunk_bytes)
    n_chunks = words.shape[0]

    k = len(blobs)
    idx_np = np.asarray(idx, np.int32)
    if idx_np.shape != (k,) or idx_np.min() < 0 or idx_np.max() >= n_chunks:
        raise ValueError("chunk indices out of range")
    rows = _rows_from_blobs(blobs, width)
    # pow2 padding alone bounds the jit cache to O(log max_rows) per
    # (C, W); a higher floor would inflate the PCIe upload at small K
    kp = pow2ceil(k)
    if kp > k:                          # idempotent duplicates of row 0
        idx_np = np.concatenate([idx_np, np.full(kp - k, idx_np[0],
                                                 np.int32)])
        rows = np.concatenate([rows, np.repeat(rows[:1], kp - k, axis=0)])
    moved = rows.nbytes + idx_np.nbytes
    idx_d = jnp.asarray(idx_np)
    rows_d = jnp.asarray(rows)
    if backend == "pallas":
        from repro.kernels.patch_scatter.kernel import patch_scatter_pallas
        out = patch_scatter_pallas(words, idx_d, rows_d,
                                   interpret=interpret)
    elif backend == "ref":
        from repro.kernels.patch_scatter.ref import patch_scatter_ref
        out = patch_scatter_ref(words, idx_d, rows_d)
    else:
        raise ValueError(f"unknown scatter backend {backend!r}")
    return _from_words(out.reshape(-1), x.dtype, x.shape), moved
