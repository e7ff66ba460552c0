"""Pallas TPU kernel: exact per-chunk dirty detection between two resident
arrays (the undo-path fast check — both versions in device memory, so a
bitwise compare is cheaper and exact vs hashing one side).

Grid: one program per chunk; streams the (R, 128) word tiles of both
inputs (``kernels/common.py``) HBM->VMEM, reduces `any(a != b)` on the
VPU, and writes the flag across one (1, 128) output row.  Bandwidth-bound
by design: 2 streams in, one row out per chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANES, tile_words


def _block_diff_kernel(a_ref, b_ref, out_ref):
    neq = (a_ref[0] != b_ref[0]).astype(jnp.int32)
    out_ref[0] = jnp.full((1, LANES), jnp.max(neq), jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_diff_pallas(a_words: jax.Array, b_words: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """a/b: uint32 [n_chunks, W]. Returns int32 [n_chunks]."""
    assert a_words.shape == b_words.shape, (a_words.shape, b_words.shape)
    a, b = tile_words(a_words), tile_words(b_words)
    n_chunks, rows, _ = a.shape
    spec = pl.BlockSpec((1, rows, LANES), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _block_diff_kernel,
        grid=(n_chunks,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 1, LANES), jnp.int32),
        interpret=interpret,
        name="block_diff_pallas",
    )(a, b)
    return out[:, 0, 0]
