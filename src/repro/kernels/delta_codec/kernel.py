"""Pallas TPU kernel: on-device bit-plane encode of the compacted buffer.

Runs right after ``delta_pack`` on the same device, turning the compacted
dirty-chunk buffer into the codec's plane stream *before* it crosses PCIe
— the host then assembles KZC1 frames (``host.py``) without ever seeing
the raw bytes.

Layout: the wrapper transposes each group of ``gw`` words so that the 32
words one plane word is built from lie along sublanes: block g is the
(32, gw/32) tile ``t[k, j] = word j*32 + k``.  Plane p's bitmap word j is
then ``OR_k ((t[k, j] >> p) & 1) << k`` — a reduction over rows (halving
to one vreg of rows, then rotate-and-or, whose result does not depend on
the rotation's direction), with no reshape to 32 lanes.

Grid: one program per group.  Each step writes its 32 bitmaps (the
bitshuffled group, same size as the input) and the group's
(stored_mask, ones_mask) pair into lanes 0/1 of a (1, 128) row.  A plane
is all-zero when no bit of it is set and all-one when every bit is; both
are scalar reductions of the plane's bits.  Compaction of the stored
planes to the front of the stream happens after the kernel, in XLA
(``ref.compact_planes``), so the kernel has no data-dependent stores.

Outputs (same contract as :func:`ref.codec_encode_ref`; the plane stream
is byte-identical to ``host.plane_split`` + compaction):
  masks   uint32 [n_groups, 2]        — (stored_mask, ones_mask)
  count   int32  [1, 1]               — total stored planes
  planes  uint32 [n_groups*32, gw/32] — stored planes compacted to the
                                        front; rows past ``count`` garbage
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, SUBLANES, lane_pair
from repro.kernels.delta_codec.ref import compact_planes

PLANES = 32


def _or_rows(v: jax.Array) -> jax.Array:
    """OR of the 32 rows of v [32, pw] -> (1, pw)."""
    rows = v.shape[0]
    while rows > SUBLANES:
        rows //= 2
        v = v[:rows] | v[rows:2 * rows]
    for s in (4, 2, 1):
        v = v | pltpu.roll(v, s, 0)
    return v[0:1]


def _codec_encode_kernel(t_ref, planes_ref, masks_ref):
    t = t_ref[0]                       # (32, pw) int32; row k = bit lane k
    k = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    smask = jnp.int32(0)
    omask = jnp.int32(0)
    for p in range(PLANES):            # unrolled: 32 static plane slots
        bits = jax.lax.shift_right_logical(t, p) & 1
        planes_ref[0, p:p + 1, :] = _or_rows(bits << k)
        some = jnp.max(bits)           # 0: all-zero plane
        every = jnp.min(bits)          # 1: all-one plane
        smask = smask | ((some & (1 - every)) << p)
        omask = omask | (every << p)
    masks_ref[0] = lane_pair(smask, omask)


@functools.partial(jax.jit, static_argnames=("gw", "interpret"))
def codec_encode_pallas(rows: jax.Array, *, gw: int,
                        interpret: bool = False):
    """rows: uint32 [R, W] with W % gw == 0, gw a power of two >= 32.

    Returns (masks [R*W//gw, 2] u32, count [1,1] i32,
    planes [R*W//gw*32, gw//32] u32) — same contract as
    :func:`ref.codec_encode_ref`."""
    r, w = rows.shape
    assert gw >= 32 and gw & (gw - 1) == 0, f"gw={gw}"
    assert w % gw == 0, (w, gw)
    ng = r * (w // gw)
    pw = gw // PLANES
    t = rows.reshape(ng, pw, PLANES).swapaxes(1, 2)
    planes, masks = pl.pallas_call(
        _codec_encode_kernel,
        grid=(ng,),
        in_specs=[pl.BlockSpec((1, PLANES, pw), lambda g: (g, 0, 0))],
        out_specs=[pl.BlockSpec((1, PLANES, pw), lambda g: (g, 0, 0)),
                   pl.BlockSpec((1, 1, LANES), lambda g: (g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((ng, PLANES, pw), jnp.int32),
                   jax.ShapeDtypeStruct((ng, 1, LANES), jnp.int32)],
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(t, jnp.int32))
    u32 = functools.partial(jax.lax.bitcast_convert_type,
                            new_dtype=jnp.uint32)
    masks = u32(masks[:, 0, :2])
    count, buf = compact_planes(u32(planes), masks[:, 0])
    return masks, count, buf
