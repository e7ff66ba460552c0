"""Chunk-granular delta planning — shared by the writer and the loader.

Both hot paths move *only the state difference* (the paper's headline):

  - the checkpoint writer serializes just the dirty byte ranges of an
    updated base buffer (checkpoint.build_manifest), and
  - the checkout loader fetches and patches just the chunks that differ
    between the live buffer and the target manifest (checkout.StateLoader).

This module holds the pieces both need: dirty-index computation from
detection hashes, run coalescing, zero-copy/device-sliced range readers,
device-side patching, and the exact (hash-free) chunk compare built on the
``block_diff`` Pallas kernel with a NumPy fallback.

Range extraction never materializes the full buffer: NumPy bases are read
through a zero-copy ``memoryview``; JAX bases are sliced on device so only
the dirty ranges cross the device→host boundary.
"""
from __future__ import annotations

import logging
import os
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs

_log = logging.getLogger(__name__)


def note_kernel_fallback(where: str, err: Exception) -> None:
    """Record one storage or replay demotion to a slower path: the active
    session's ``kishu_kernel_fallbacks_total`` (snapshotted into
    WriteStats/CheckoutStats per operation), and a warning the first time
    in that session — a silently slow path must be visible without turning
    every commit into log spam.  Outside any session nothing is counted
    and each call logs."""
    o = obs.active()
    if o is None or o.note_kernel_fallback(where):
        _log.warning(
            "device kernel unavailable in %s (%s: %s); using the host path. "
            "Logged once per session — see the kernel_fallbacks counter in "
            "WriteStats/CheckoutStats for the running total.",
            where, type(err).__name__, err)


def note_kernel_call(kernel: str, backend: str) -> None:
    """Count one dispatch of a device kernel by the backend that ran it
    (``kishu_kernel_calls_total{kernel,backend}`` in the active session's
    registry) — how a run shows whether the Pallas kernel or the jnp
    reference did the work."""
    obs.count("kishu_kernel_calls_total", kernel=kernel, backend=backend)


def kernel_fallbacks() -> int:
    """The active session's kernel fallbacks so far (0 outside a
    session)."""
    o = obs.active()
    return 0 if o is None else o.kernel_fallbacks()


def dirty_indices(prev_hex: Sequence[str], cur_hex: Sequence[str]) -> List[int]:
    """Chunk indices whose detection hash differs (index-aligned compare).
    Indices present on only one side count as dirty."""
    n = max(len(prev_hex), len(cur_hex))
    return [i for i in range(n)
            if i >= len(prev_hex) or i >= len(cur_hex)
            or prev_hex[i] != cur_hex[i]]


def coalesce(indices: Sequence[int]) -> List[Tuple[int, int]]:
    """Sorted chunk indices -> [start, stop) runs, merging adjacency (one
    device slice / one store range per run instead of one per chunk)."""
    runs: List[Tuple[int, int]] = []
    for i in sorted(indices):
        if runs and runs[-1][1] == i:
            runs[-1] = (runs[-1][0], i + 1)
        else:
            runs.append((i, i + 1))
    return runs


def chunk_offsets(chunks: Sequence[dict]) -> List[int]:
    """Byte offset of each chunk in the assembled base blob."""
    offs, pos = [], 0
    for c in chunks:
        offs.append(pos)
        pos += int(c["n"])
    return offs


# ---------------------------------------------------------------------------
# dirty-range readers (writer side)
# ---------------------------------------------------------------------------

def range_reader(base: Any, chunk_bytes: int) -> Optional[Callable[[int, int], bytes]]:
    """Callable ``(lo, hi) -> bytes`` over the logical byte image of an
    array base, moving only the requested range; ``None`` when the leaf
    cannot be range-read (non-array, non-contiguous, unaligned chunking) —
    callers then fall back to full serialization.

    Ranges must start on a ``chunk_bytes`` boundary; the final range may end
    at the buffer length.  The byte image matches ``leaf_to_bytes`` (C-order
    raw bytes), so range-read chunks are bit-identical to full-path chunks.
    """
    import jax

    from repro.core.serialize import is_prng_key

    if isinstance(base, np.ndarray):
        if not base.flags["C_CONTIGUOUS"]:
            return None
        try:
            mv = memoryview(base).cast("B")
        except (TypeError, ValueError, BufferError):
            return None
        return lambda lo, hi: bytes(mv[lo:hi])

    if isinstance(base, jax.Array) and not is_prng_key(base):
        dt = np.dtype(base.dtype)
        item = dt.itemsize
        if item <= 0 or chunk_bytes % item:
            return None
        flat = base.reshape(-1)
        total = flat.shape[0] * item

        def read(lo: int, hi: int) -> bytes:
            hi = min(hi, total)
            # element-aligned by construction: lo is a chunk boundary and
            # hi is a chunk boundary or the buffer end
            seg = flat[lo // item: -(-hi // item)]
            return np.asarray(seg).tobytes()[: hi - lo]

        return read
    return None


# ---------------------------------------------------------------------------
# fused on-device delta pack (writer side, DESIGN.md §15)
# ---------------------------------------------------------------------------

def device_kernel_applies(base: Any) -> bool:
    """Whether the chunk kernels can take ``base``: a jax array (not a PRNG
    key) held whole by one device, of a dtype the word bitcast handles and
    with at least one byte.  Anything else takes the host path."""
    import jax

    from repro.core.serialize import is_prng_key
    from repro.kernels.chunk_hash.ops import words_supported

    # an array sharded or replicated over several devices is refused here,
    # explicitly: the kernels work on one array on one device
    return (isinstance(base, jax.Array) and not is_prng_key(base)
            and len(base.sharding.device_set) == 1
            and words_supported(base.dtype) and base.size > 0)


def device_delta_pack(base: Any, prev_hashes, chunk_bytes: int):
    """One fused pass over a device array: detection hashes, dirty indices,
    and a *compacted* dirty-chunk buffer still on device — only dirty bytes
    ever cross device→host (``DeltaPack.read_chunks``).  The Pallas kernel
    runs on a TPU, the jnp reference elsewhere.

    Returns ``None`` whenever the fused path doesn't apply to the input —
    not a one-device jax array (``device_kernel_applies``), non-power-of-two
    chunking, no/mismatched previous hashes — and the caller hashes another
    way (``chunk_hashes_device`` → ``chunk_hashes_np`` + range_reader).  A
    kernel error raises.  Only engaged off-CPU by default — on CPU the
    NumPy path is faster — override with ``KISHU_DEVICE_DELTA=1/0``.
    """
    if prev_hashes is None or chunk_bytes % 4 \
            or chunk_bytes & (chunk_bytes - 1):
        return None
    env = os.environ.get("KISHU_DEVICE_DELTA", "").strip()
    if env == "0":
        return None
    import jax

    if env != "1" and jax.default_backend() == "cpu":
        return None
    if not device_kernel_applies(base):
        return None
    nbytes = int(base.size) * np.dtype(base.dtype).itemsize
    n_chunks = -(-nbytes // chunk_bytes)
    prev = np.asarray(prev_hashes, dtype=np.uint64).reshape(-1)
    if prev.shape[0] != n_chunks:
        return None                      # structure changed: no valid diff
    from repro.kernels.common import platform_backend
    from repro.kernels.delta_pack.ops import delta_pack

    backend = platform_backend(base)
    note_kernel_call("delta_pack", backend)
    with obs.span("delta_pack", nbytes=nbytes):
        return delta_pack(base, prev, chunk_bytes, backend=backend)


# ---------------------------------------------------------------------------
# chunk patching (loader side)
# ---------------------------------------------------------------------------

def patch_numpy_base(base: np.ndarray, segs: Sequence[Tuple[int, bytes]]
                     ) -> np.ndarray:
    """Write byte segments into a live base buffer in place (views and
    aliases into it stay valid).  Returns the same object."""
    mv = memoryview(base).cast("B")
    for off, data in segs:
        mv[off:off + len(data)] = data
    return base


def patch_device_chunks(base: Any, segs: Sequence[Tuple[int, bytes]],
                        chunk_bytes: int) -> Optional[Tuple[Any, int]]:
    """Fused checkout scatter: upload all dirty chunks of a device array as
    one compacted buffer and land them in a single pass
    (kernels/patch_scatter: the Pallas kernel on a TPU, the jnp reference
    elsewhere) — the mirror image of ``device_delta_pack``.

    Returns ``(patched array, bytes moved host→device)``, or ``None``
    whenever the fused path doesn't apply to the input — not a one-device
    jax array of a word-bitcastable dtype, non-chunk-aligned or partial
    segments, or the env veto — and the caller patches with the per-chunk
    ``patch_device_array`` loop below.  A kernel error raises.  Only
    engaged off-CPU by default (the jnp loop wins on CPU); override with
    ``KISHU_DEVICE_SCATTER=1/0``.
    """
    if not segs or chunk_bytes <= 0 or chunk_bytes % 4:
        return None
    env = os.environ.get("KISHU_DEVICE_SCATTER", "").strip()
    if env == "0":
        return None
    import jax

    if env != "1" and jax.default_backend() == "cpu":
        return None
    if not device_kernel_applies(base):
        return None
    nbytes = int(base.size) * np.dtype(base.dtype).itemsize
    n_chunks = -(-nbytes // chunk_bytes)
    idx: List[int] = []
    blobs: List[bytes] = []
    for off, data in sorted(segs):
        if off % chunk_bytes:
            return None                  # not chunk-aligned: DUS path
        i = off // chunk_bytes
        want = min((i + 1) * chunk_bytes, nbytes) - off
        if i >= n_chunks or len(data) != want:
            return None                  # partial chunk: DUS path
        idx.append(i)
        blobs.append(data)
    from repro.kernels.common import platform_backend
    from repro.kernels.patch_scatter.ops import scatter_chunks

    backend = platform_backend(base)
    note_kernel_call("patch_scatter", backend)
    with obs.span("scatter_dev", chunks=len(idx)):
        return scatter_chunks(base, idx, blobs, chunk_bytes, backend=backend)


def patch_device_array(base: Any, segs: Sequence[Tuple[int, bytes]]) -> Any:
    """Patch a device array by updating only the dirty element ranges on
    device: the only host→device traffic is the dirty bytes themselves.
    Segments must be element-aligned (checked by the planner).  Returns a
    new array (device buffers are immutable)."""
    import jax
    import jax.numpy as jnp

    dt = np.dtype(base.dtype)
    item = dt.itemsize
    flat = base.reshape(-1)
    # merge adjacent segments: one dynamic_update_slice per contiguous run
    # (accumulate parts and join once — a long dirty run must not devolve
    # into quadratic bytes concatenation)
    merged: List[Tuple[int, List[bytes]]] = []
    end = -1
    for off, data in sorted(segs):
        if merged and end == off:
            merged[-1][1].append(data)
        else:
            merged.append((off, [data]))
        end = off + len(data)
    moved = 0
    for off, parts in merged:
        seg = np.frombuffer(b"".join(parts), dtype=dt)
        moved += seg.nbytes
        flat = jax.lax.dynamic_update_slice(
            flat, jnp.asarray(seg), (off // item,))
    obs.count("kishu_h2d_bytes_total", moved, path="patch")
    return flat.reshape(base.shape)


# ---------------------------------------------------------------------------
# exact chunk compare (hash-free cross-check)
# ---------------------------------------------------------------------------

def exact_dirty_indices(a: Any, b: Any, chunk_bytes: int) -> List[int]:
    """Chunk indices where ``a`` and ``b`` differ bitwise — the exact
    (collision-free) answer the detection hashes approximate.  Device
    arrays the kernels take are compared on device by ``block_diff`` (the
    Pallas kernel on a TPU, the jnp reference elsewhere); anything else by
    a NumPy byte compare.  Used by tests and paranoid verification to
    cross-check hash-planned deltas."""
    if device_kernel_applies(a) and device_kernel_applies(b) \
            and chunk_bytes % 4 == 0 and chunk_bytes & (chunk_bytes - 1) == 0:
        from repro.kernels.block_diff.ops import dirty_chunks
        from repro.kernels.common import platform_backend
        note_kernel_call("block_diff", platform_backend(a))
        return [int(i) for i in dirty_chunks(a, b, chunk_bytes)]
    ba = np.ascontiguousarray(np.asarray(a)).reshape(-1).view(np.uint8)
    bb = np.ascontiguousarray(np.asarray(b)).reshape(-1).view(np.uint8)
    if ba.size != bb.size:
        raise ValueError("exact_dirty_indices: size mismatch")
    n_chunks = max(-(-ba.size // chunk_bytes), 1) if ba.size else 0
    out = []
    for i in range(n_chunks):
        lo, hi = i * chunk_bytes, min((i + 1) * chunk_bytes, ba.size)
        if not np.array_equal(ba[lo:hi], bb[lo:hi]):
            out.append(i)
    return out
