"""Pallas TPU kernel: per-chunk detection hash at HBM bandwidth.

Grid: one program per chunk.  Each program streams one chunk HBM->VMEM as
an (R, 128) tile of 32-bit words (``kernels/common.py``), avalanche-mixes
every word with its position (pure VPU ops: xor/mul/shift), XOR-reduces
the tile, folds in the true byte length, and writes the 2x32-bit hash pair
into lanes 0 and 1 of a (1, 128) output row.

The XOR reduction halves the rows down to one (8, 128) vreg, then rotates
and xors within it (``pltpu.roll``; xor is commutative, so the direction
of the rotation does not matter) — no sequential dependency, unlike FNV,
which is why this hash was chosen for the TPU (DESIGN.md §4).  Arithmetic
is int32: multiplication and xor wrap exactly like uint32, and right
shifts are logical, so the bits are those of ``hashing.chunk_hashes_np``.

The chunk's true byte count comes in through scalar prefetch (SMEM, one
word per chunk); ``MAX_CALL_CHUNKS`` bounds the chunks per call so it
stays far below SMEM's 1 MiB.  VMEM: the double-buffered input tile (8 * W bytes) plus the
mixing temporaries of one tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import C1, C2, GOLDEN, SEEDS
from repro.kernels.common import (LANES, SUBLANES, TILE_WORDS, lane_pair,
                                  tile_words)

MAX_CALL_CHUNKS = 1 << 15        # SMEM words of nbytes per pallas_call


def as_i32(c) -> int:
    """A uint32 constant as the int32 with the same bits."""
    return int(np.uint32(c).view(np.int32))


def xor_all(v: jax.Array) -> jax.Array:
    """XOR of every element of v [R, 128] (R a power of two >= 8), as a
    (1, 128) vector whose lanes all hold it."""
    rows = v.shape[0]
    while rows > SUBLANES:
        rows //= 2
        v = v[:rows] ^ v[rows:2 * rows]
    for s in (4, 2, 1):
        v = v ^ pltpu.roll(v, s, 0)
    for s in (64, 32, 16, 8, 4, 2, 1):
        v = v ^ pltpu.roll(v, s, 1)
    return v[0:1]


def hash_tile(w: jax.Array, nbytes) -> tuple:
    """Detection hash lanes (h0, h1) of one chunk tile w [R, 128] int32
    holding ``nbytes`` valid bytes; each a (1, 128) int32 vector with all
    lanes equal.  Words past the valid ones (zero padding) contribute 0."""
    rows = w.shape[0]
    idx = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    n_valid = (nbytes + 3) // 4
    srl = jax.lax.shift_right_logical
    out = []
    for seed in SEEDS:
        m = (w ^ (idx * as_i32(GOLDEN) + as_i32(seed))) * as_i32(C1)
        m = m ^ srl(m, 16)
        m = m * as_i32(C2)
        m = m ^ srl(m, 13)
        m = jnp.where(idx < n_valid, m, 0)
        h = (xor_all(m) ^ nbytes) * as_i32(C1)
        out.append(h ^ srl(h, 16))
    return out[0], out[1]


def _chunk_hash_kernel(nbytes_ref, words_ref, out_ref):
    h0, h1 = hash_tile(words_ref[0], nbytes_ref[pl.program_id(0)])
    out_ref[0] = lane_pair(h0, h1)


def _hash_call(tiles: jax.Array, nbytes: jax.Array, interpret: bool):
    n, rows, _ = tiles.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, rows, LANES), lambda i, nb: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda i, nb: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _chunk_hash_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, LANES), jnp.int32),
        interpret=interpret,
        name="chunk_hash_pallas",
    )(nbytes, tiles)
    return out[:, 0, :2]


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunk_hash_pallas(words: jax.Array, nbytes: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """words: uint32 [n_chunks, W] (W a power of two) or its word tiles
    [n_chunks, R, 128]; nbytes: int32 [n_chunks].  Returns uint32
    [n_chunks, 2]."""
    if words.ndim == 2:
        wsize = words.shape[1]
        assert wsize & (wsize - 1) == 0, f"W={wsize} must be a power of two"
        words = tile_words(words, TILE_WORDS)
    tiles = jax.lax.bitcast_convert_type(words, jnp.int32)
    nbytes = nbytes.astype(jnp.int32)
    parts = [_hash_call(tiles[s:s + MAX_CALL_CHUNKS],
                        nbytes[s:s + MAX_CALL_CHUNKS], interpret)
             for s in range(0, tiles.shape[0], MAX_CALL_CHUNKS)]
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return jax.lax.bitcast_convert_type(out, jnp.uint32)
