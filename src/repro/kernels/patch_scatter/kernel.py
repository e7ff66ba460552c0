"""Pallas TPU kernel: scatter compacted dirty rows into a chunked array.

The checkout mirror of ``delta_pack``'s compaction: the host uploads the
K dirty chunks of a co-variable as one compacted buffer (plus a K int32
row->chunk index vector) and a single pass lands every row at its chunk
slot — replacing the per-chunk ``dynamic_update_slice`` loop, whose K
separate dispatches each copy the whole array.

Layout: chunks are (R, 128) word tiles (``kernels/common.py``), so a block
of one chunk is ``(1, R, 128)`` — the array's own last two dimensions.

Grid: one program per dirty row.  The chunk index vector rides in as a
scalar-prefetch operand (``PrefetchScalarGridSpec``), so the *output*
BlockSpec can be data-dependent: program k maps its output block to chunk
``idx[k]``.  The live array is an un-blocked (``pl.ANY``) input aliased to
the output (``input_output_aliases``): it is never read, and blocks no
program writes keep their contents — only ``K * W * 4`` bytes move, not
``C * W * 4``.

Duplicate indices are allowed only when they carry identical rows (the ops
layer pads K to a power of two by repeating row 0) — the grid is
sequential per core, so the last write wins deterministically anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, tile_words


def _scatter_kernel(idx_ref, words_ref, rows_ref, out_ref):
    del idx_ref, words_ref                 # routing happens in the BlockSpecs
    out_ref[...] = rows_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def patch_scatter_pallas(words: jax.Array, idx: jax.Array, rows: jax.Array,
                         *, interpret: bool = False) -> jax.Array:
    """words u32 [C, W]; idx i32 [K] (values in [0, C)); rows u32 [K, W].

    Returns words with words[idx[k]] = rows[k]; untouched chunks preserved
    via output aliasing."""
    c, w = words.shape
    k, wr = rows.shape
    assert wr == w, (wr, w)
    assert idx.shape == (k,), (idx.shape, k)
    tiles = tile_words(words)
    r = tiles.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, r, LANES), lambda i, idx_ref: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, LANES),
                               lambda i, idx_ref: (idx_ref[i], 0, 0)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.uint32),
        input_output_aliases={1: 0},       # words (first non-scalar) -> out
        interpret=interpret,
    )(idx, tiles, tile_words(rows))
    return out.reshape(c, r * LANES)[:, :w]
