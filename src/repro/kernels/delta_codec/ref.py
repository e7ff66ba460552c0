"""jnp reference encoder for the bit-plane codec.

Same contract as :func:`kernel.codec_encode_pallas` and the same plane
stream as ``host.bitplane_compress``: stored planes are compacted to the
front in (group, plane) order (:func:`compact_planes`, which the kernel's
wrapper shares), so device payloads are byte-identical to host payloads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def compact_planes(planes: jax.Array, stored_mask: jax.Array):
    """planes uint32 [ng, 32, pw]; stored_mask uint32 [ng] -> (count
    int32 [1, 1], planes [ng*32, pw] with the stored ones first in
    (group, plane) order)."""
    ng, n_planes, pw = planes.shape
    shifts = jnp.arange(n_planes, dtype=jnp.uint32)
    flags = ((stored_mask[:, None] >> shifts) & 1).astype(bool).reshape(-1)
    order = jnp.argsort(~flags, stable=True)                 # stored first
    count = jnp.sum(flags.astype(jnp.int32)).reshape(1, 1)
    return count, planes.reshape(ng * n_planes, pw)[order]


@functools.partial(jax.jit, static_argnames=("gw",))
def codec_encode_ref(rows: jax.Array, *, gw: int):
    """Encode uint32 ``rows`` [R, W] (W % gw == 0) into bit-planes.

    Returns:
      masks  uint32 [R * W//gw, 2]  — (stored_mask, ones_mask) per group
      count  int32  [1, 1]          — number of stored planes
      planes uint32 [R * W//gw * 32, gw//32] — stored planes compacted to
                                      the front in (group, plane) order
    """
    r, w = rows.shape
    gpr = w // gw
    ng = r * gpr
    pw = gw // 32
    grouped = rows.reshape(ng, pw, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    per_plane = []
    for p in range(32):
        bits = (grouped >> jnp.uint32(p)) & jnp.uint32(1)
        per_plane.append(jnp.sum(bits << shifts, axis=2, dtype=jnp.uint32))
    planes = jnp.stack(per_plane, axis=1)                    # [ng, 32, pw]
    zero = jnp.all(planes == 0, axis=2)
    ones = jnp.all(planes == jnp.uint32(0xFFFFFFFF), axis=2)
    store = (~zero) & (~ones)                                # [ng, 32]
    smask = jnp.sum(jnp.where(store, jnp.uint32(1) << shifts, 0),
                    axis=1, dtype=jnp.uint32)
    omask = jnp.sum(jnp.where(ones, jnp.uint32(1) << shifts, 0),
                    axis=1, dtype=jnp.uint32)
    count, buf = compact_planes(planes, smask)
    return jnp.stack([smask, omask], axis=1), count, buf
