"""Pallas TPU kernel: the fused on-device delta pipeline.

One pass over HBM per chunk does everything the checkpoint writer's
detection+extraction hot path needs:

  hash      — avalanche-mix + XOR-reduce each chunk tile into the 2x32-bit
              detection hash pair (``chunk_hash.kernel.hash_tile``; the
              spec lives in repro.core.hashing)
  diff      — compare the pair against the *previous* commit's pair for
              that chunk (scalar-prefetched into SMEM)
  compact   — dirty chunks are copied, in chunk order, to a compacted
              buffer at a running-counter row, so the caller transfers
              ``count`` rows device→host instead of the whole array

Grid: one program per chunk, executed sequentially per core (the TPU grid
contract), which makes the SMEM running counter a legal cross-step
accumulator.  Each step streams one (R, 128) word tile in, writes the hash
pair into lanes 0/1 of a (1, 128) row and, when the chunk is dirty, DMAs
the tile (already in VMEM from the hash read — no second HBM fetch) to its
row of the compacted buffer, which stays in HBM.  So VMEM holds only the
double-buffered input tile, whatever the segment size.

The kernel computes in int32 (bit-identical to uint32 for this hash).
``delta_pack_pallas`` returns the same five outputs as ``ref.delta_pack_ref``:
  hashes  uint32 [n_chunks, 2]   — detection hash pairs (lane 0 = high word)
  dirty   int32  [n_chunks, 1]   — 1 iff the pair differs from ``prev``
  pos     int32  [n_chunks, 1]   — row of the chunk in the compacted buffer,
                                   -1 when clean
  count   int32  [1, 1]          — total dirty chunks (valid rows of ``buf``)
  buf     uint32 [n_chunks, R, 128] — compacted dirty chunk tiles; rows past
                                   ``count`` are unwritten garbage
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chunk_hash.kernel import hash_tile
from repro.kernels.common import LANES, TILE_WORDS, lane_pair, tile_words


def _delta_pack_kernel(nbytes_ref, prev_ref, words_ref, hash_ref, buf_ref,
                       cnt_ref, sem):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        cnt_ref[0] = 0                 # running compaction counter (SMEM
                                       # scratch persists across grid steps)

    h0, h1 = hash_tile(words_ref[0], nbytes_ref[i])
    hash_ref[0] = lane_pair(h0, h1)
    ne = (h0 != prev_ref[2 * i]) | (h1 != prev_ref[2 * i + 1])
    dirty = jnp.max(ne.astype(jnp.int32))
    pos = cnt_ref[0]

    @pl.when(dirty > 0)
    def _():
        copy = pltpu.make_async_copy(words_ref, buf_ref.at[pl.ds(pos, 1)],
                                     sem)
        copy.start()
        copy.wait()

    cnt_ref[0] = pos + dirty


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_pack_pallas(words: jax.Array, prev: jax.Array, nbytes: jax.Array,
                      *, interpret: bool = False):
    """words: uint32 [n_chunks, W] (W a power of two) or its word tiles
    [n_chunks, R, 128] (R >= 8); prev: uint32 [n_chunks, 2] previous hash
    pairs; nbytes: int32 [n_chunks].

    Returns (hashes [n,2] u32, dirty [n,1] i32, pos [n,1] i32,
    count [1,1] i32, buf [n,R,128] u32)."""
    if words.ndim == 2:
        wsize = words.shape[1]
        assert wsize & (wsize - 1) == 0, f"W={wsize} must be a power of two"
        words = tile_words(words, TILE_WORDS)
    n_chunks, rows, _ = words.shape
    assert prev.shape == (n_chunks, 2), (prev.shape, n_chunks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec((1, rows, LANES),
                               lambda i, nb, pv: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, LANES), lambda i, nb, pv: (i, 0, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
    )
    i32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    hash_rows, buf = pl.pallas_call(
        _delta_pack_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_chunks, 1, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((n_chunks, rows, LANES), jnp.int32)],
        interpret=interpret,
    )(nbytes.astype(jnp.int32), i32(prev).reshape(-1), i32(words))
    u32 = functools.partial(jax.lax.bitcast_convert_type,
                            new_dtype=jnp.uint32)
    hashes = u32(hash_rows[:, 0, :2])
    dirty = jnp.any(hashes != prev, axis=1)
    d32 = dirty.astype(jnp.int32)
    cum = jnp.cumsum(d32)
    pos = jnp.where(dirty, cum - 1, -1).astype(jnp.int32)
    return hashes, d32[:, None], pos[:, None], cum[-1:][:, None], u32(buf)
