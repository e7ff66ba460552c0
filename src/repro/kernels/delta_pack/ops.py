"""Public wrapper for the fused on-device delta pipeline.

``delta_pack(x, prev_hashes, chunk_bytes)`` runs one fused pass (hash +
diff + compaction) over a device array and returns a :class:`DeltaPack`:
the new detection hashes, the dirty-chunk index vector, and handles to the
*compacted* dirty-chunk buffers still resident on device.  The checkpoint
writer then streams only the dirty rows host-side via
:meth:`DeltaPack.read_chunks`, double-buffered (``copy_to_host_async`` of
segment *i+1* is issued before segment *i*'s rows are consumed) so the
device→host DMA overlaps the backend ``put_chunks`` upload.

Segments: the wrapper cuts the array into super-blocks of at most
``seg_bytes`` (default 4 MiB) of chunks and launches one ``pallas_call``
per segment — at most two jit shapes (full segments + the tail) whatever
the array size, a bounded SMEM operand of previous hashes per call, and a
unit of double-buffered device→host transfer.  The kernel keeps the
compacted rows in HBM, so VMEM use does not grow with the segment.

Traffic accounting: ``bytes_transferred`` counts every byte this pack moved
device→host — 12 bytes/chunk of metadata (8 hash + 4 dirty flag) plus the
compacted rows actually materialized.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import hashing
from repro.kernels.chunk_hash.ops import chunk_nbytes, chunk_rows
from repro.kernels.common import TILE_WORDS, platform_backend, tile_words

DEFAULT_SEG_BYTES = 4 << 20      # bytes of chunks per kernel launch


@dataclass
class _Seg:
    start: int                   # first chunk index covered by this segment
    stop: int
    dirty: np.ndarray            # global indices of dirty chunks, ascending
    buf: Any                     # device uint32 [len(dirty), ...] compacted
                                 # rows (word tiles on the kernel path)


@dataclass
class DeltaPack:
    """Result of one fused delta pass: detection hashes + dirty indices on
    host, compacted dirty-chunk buffers still on device."""
    nbytes: int
    chunk_bytes: int
    n_chunks: int
    hashes: np.ndarray           # uint64 [n_chunks] detection hashes
    dirty: np.ndarray            # ascending global dirty-chunk indices
    bytes_transferred: int = 0   # device→host bytes moved so far
    codec_chunks_encoded: int = 0    # chunks that crossed PCIe as frames
    codec_chunks_skipped: int = 0    # probe veto / frame larger than raw
    _segments: List[_Seg] = field(default_factory=list)

    @property
    def count(self) -> int:
        return int(self.dirty.size)

    @property
    def dirty_set(self) -> set:
        return set(int(i) for i in self.dirty)

    def _chunk_len(self, i: int) -> int:
        return min((i + 1) * self.chunk_bytes, self.nbytes) \
            - i * self.chunk_bytes

    def _plan(self, indices: Optional[Iterable[int]]
              ) -> List[Tuple[_Seg, List[int]]]:
        """Per-segment read plan for the requested dirty chunks."""
        want = sorted(set(int(i) for i in indices)) if indices is not None \
            else [int(i) for i in self.dirty]
        if not want:
            return []
        bad = [i for i in want if not (0 <= i < self.n_chunks)]
        assert not bad, f"chunk indices out of range: {bad[:4]}"
        plan: List[Tuple[_Seg, List[int]]] = []
        for seg in self._segments:
            sel = [i for i in want if seg.start <= i < seg.stop]
            if not sel:
                continue
            rowmap = {int(ci): r for r, ci in enumerate(seg.dirty)}
            missing = [i for i in sel if i not in rowmap]
            if missing:
                raise KeyError(f"chunks {missing[:4]} are not dirty in "
                               f"this pack")
            plan.append((seg, sel))
        return plan

    def read_chunks(self, indices: Optional[Iterable[int]] = None
                    ) -> Iterator[Tuple[int, bytes]]:
        """Yield ``(chunk_index, chunk_bytes)`` for the requested dirty
        chunks in ascending index order, moving only compacted rows.

        Double-buffered: before segment *i*'s rows are materialized (a
        blocking ``np.asarray``), segment *i+1*'s ``copy_to_host_async`` is
        already in flight — so while the caller hashes/uploads segment *i*'s
        chunks, the next segment's DMA proceeds in parallel.
        """
        plan = self._plan(indices)
        if plan:
            try:                    # prime the pipeline
                plan[0][0].buf.copy_to_host_async()
            except AttributeError:
                pass
        for k, (seg, sel) in enumerate(plan):
            if k + 1 < len(plan):
                try:                # overlap: next DMA behind this upload
                    plan[k + 1][0].buf.copy_to_host_async()
                except AttributeError:
                    pass
            with obs.span("d2h", bytes=int(seg.buf.nbytes)):
                host = np.asarray(seg.buf)      # blocks on this segment only
            self.bytes_transferred += host.nbytes
            obs.count("kishu_d2h_bytes_total", host.nbytes, path="pack")
            obs.count("kishu_host_syncs_total", where="read_chunks")
            rowmap = {int(ci): r for r, ci in enumerate(seg.dirty)}
            raw = host.reshape(host.shape[0], -1).view(np.uint8)
            for ci in sel:
                row = raw[rowmap[ci]]
                yield ci, row[: self._chunk_len(ci)].tobytes()

    def read_chunks_encoded(self, indices: Optional[Iterable[int]] = None
                            ) -> Iterator[Tuple[int, bytes,
                                                Optional[bytes]]]:
        """Like :meth:`read_chunks`, but compress each segment *on device*
        with the bit-plane codec (kernels/delta_codec) before it crosses
        PCIe: yields ``(chunk_index, logical_bytes, stored_frame)`` where
        ``stored_frame`` is a ready-to-store KZC1 frame (None when the
        chunk went raw — codec off, probe veto, or the frame would not
        save bytes).  Chunk keys stay logical-byte: the logical bytes are
        reconstructed host-side from the frame itself.

        Device→host traffic per segment is 8 bytes/group of masks plus only
        the *stored* planes — the compacted rows themselves never cross.
        A tiny word sample (a few hundred bytes) is pulled first to skip
        the encode entirely for incompressible data.
        """
        from repro.kernels.delta_codec import host as codec_host
        from repro.kernels.delta_codec import ops as codec_ops

        plan = self._plan(indices)
        if not plan:
            return
        width = self.chunk_bytes // 4
        engage = (codec_ops.device_codec_enabled()
                  and width >= codec_host.MIN_GROUP_WORDS
                  and codec_ops.probe_device_rows(plan[0][0].buf))
        if not engage:
            self.codec_chunks_skipped += sum(len(sel) for _, sel in plan)
            for ci, data in self.read_chunks(indices):
                yield ci, data, None
            return

        # phase 1: launch every segment's encode, overlap plane DMA
        from repro.core.delta import note_kernel_call

        backend = platform_backend(plan[0][0].buf)
        enc: List[tuple] = []
        for seg, _sel in plan:
            note_kernel_call("delta_codec", backend)
            with obs.span("encode_dev", rows=int(seg.dirty.size)):
                masks, planes_dev, gw = codec_ops.encode_rows(
                    seg.buf, width=width, backend=backend)
            try:
                planes_dev.copy_to_host_async()
            except AttributeError:
                pass
            enc.append((masks, planes_dev, gw))

        # phase 2: materialize plane streams, assemble per-chunk frames
        for k, (seg, sel) in enumerate(plan):
            masks, planes_dev, gw = enc[k]
            with obs.span("d2h", bytes=int(planes_dev.nbytes)):
                planes = np.asarray(planes_dev)  # blocks on this DMA only
            self.bytes_transferred += masks.nbytes + planes.nbytes
            obs.count("kishu_d2h_bytes_total", planes.nbytes, path="codec")
            obs.count("kishu_host_syncs_total", where="read_chunks")
            gpr = width // gw
            frames = codec_host.frames_from_encoded(
                masks, planes, gpr, gw,
                [self._chunk_len(int(ci)) for ci in seg.dirty])
            rowmap = {int(ci): r for r, ci in enumerate(seg.dirty)}
            for ci in sel:
                frame = frames[rowmap[ci]]
                logical = codec_host.bitplane_decompress(
                    frame[codec_host._FRAME_HDR:])
                if len(frame) < len(logical):
                    self.codec_chunks_encoded += 1
                    yield ci, logical, frame
                else:                   # frame saves nothing: store raw
                    self.codec_chunks_skipped += 1
                    yield ci, logical, None


@functools.partial(jax.jit, static_argnames=("chunk_bytes", "tiled"))
def _pack_words(x, chunk_bytes: int, tiled: bool):
    """Word rows [n_chunks, W] of ``x``, as (R, 128) tiles for the kernel."""
    rows = chunk_rows(x, chunk_bytes)
    return tile_words(rows, TILE_WORDS) if tiled else rows


def delta_pack(x, prev_hashes, chunk_bytes: int = 1 << 18, *,
               backend: str = "pallas", interpret: bool = False,
               seg_bytes: int = DEFAULT_SEG_BYTES) -> DeltaPack:
    """Fused hash + diff + compaction of a device array against the previous
    commit's detection hashes.

    ``prev_hashes`` is uint64 [n_chunks] (the previous LeafRecord's
    ``base_hashes``); ``chunk_bytes`` must be a power-of-two multiple of 4.
    The returned hashes are bit-identical to ``hashing.chunk_hashes_np``.
    ``backend`` is "pallas" (the TPU kernel) or "ref" (the jnp reference).
    """
    from repro.kernels.delta_pack.kernel import delta_pack_pallas
    from repro.kernels.delta_pack.ref import delta_pack_ref

    assert chunk_bytes % 4 == 0 and chunk_bytes & (chunk_bytes - 1) == 0
    nbytes_total = int(x.size) * np.dtype(x.dtype).itemsize
    if nbytes_total == 0:
        return DeltaPack(nbytes=0, chunk_bytes=chunk_bytes, n_chunks=0,
                         hashes=np.zeros((0,), np.uint64),
                         dirty=np.zeros((0,), np.int64))
    n_chunks = -(-nbytes_total // chunk_bytes)
    prev = np.asarray(prev_hashes, dtype=np.uint64).reshape(-1)
    assert prev.shape == (n_chunks,), (prev.shape, n_chunks)
    words = _pack_words(x, chunk_bytes, backend == "pallas")
    prev32 = jnp.asarray(hashing.split_u64(prev))
    nb_np = chunk_nbytes(nbytes_total, chunk_bytes)
    if backend == "pallas":
        fn = functools.partial(delta_pack_pallas, interpret=interpret)
    else:
        fn = delta_pack_ref

    seg_chunks = max(1, seg_bytes // chunk_bytes)
    segs: List[_Seg] = []
    hash_parts: List[np.ndarray] = []
    dirty_parts: List[np.ndarray] = []
    moved = 0
    for s0 in range(0, n_chunks, seg_chunks):
        s1 = min(s0 + seg_chunks, n_chunks)
        h, d, _pos, cnt, buf = fn(words[s0:s1], prev32[s0:s1],
                                  jnp.asarray(nb_np[s0:s1]))
        # the host waits here, three times a segment, for the pass
        synced = int(cnt.nbytes + d.nbytes + h.nbytes)
        with obs.span("pack_sync", bytes=synced):
            count = int(np.asarray(cnt)[0, 0])
            dflags = np.asarray(d).reshape(-1)
            hash_parts.append(np.asarray(h))
        moved += synced                      # hash pair + flag (+ count)
        obs.count("kishu_d2h_bytes_total", synced, path="pack")
        obs.count("kishu_host_syncs_total", 3, where="delta_pack")
        gdirty = s0 + np.flatnonzero(dflags).astype(np.int64)
        assert gdirty.size == count, (gdirty.size, count)
        # trim to the valid compacted rows on device — only these rows ever
        # cross device→host (read_chunks)
        segs.append(_Seg(start=s0, stop=s1, dirty=gdirty, buf=buf[:count]))
        dirty_parts.append(gdirty)
    hashes = hashing.combine_u64(np.concatenate(hash_parts, axis=0))
    dirty = np.concatenate(dirty_parts) if dirty_parts else \
        np.zeros((0,), np.int64)
    return DeltaPack(nbytes=nbytes_total, chunk_bytes=chunk_bytes,
                     n_chunks=n_chunks, hashes=hashes, dirty=dirty,
                     bytes_transferred=moved, _segments=segs)
