"""numpy-in / device-out wrappers for the on-device bit-plane encoder.

``encode_rows`` runs the device encode on a compacted dirty-chunk buffer
(one ``delta_pack`` segment) and returns the masks on host plus the plane
stream still *on device* — the caller overlaps its transfer with the next
segment's encode, mirroring ``DeltaPack.read_chunks``'s double buffering.

Row counts vary per commit, so rows are padded to the next power of two
before the jit'd encode — padded zero rows classify as all-zero planes and
contribute nothing to masks or the plane stream, and the compile cache
stays O(log max_rows) per (W, gw).
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

from repro import obs
from repro.kernels.delta_codec import host

_MIN_ROW_PAD = 8


def device_codec_enabled() -> bool:
    """KISHU_DEVICE_CODEC: "0" disables, anything else (or unset) leaves
    the codec on whenever the device pack pipeline is engaged."""
    return os.environ.get("KISHU_DEVICE_CODEC", "1") != "0"


def group_words_for(width: int) -> int:
    """Device group size for a W-word chunk row: one group per row when the
    row fits a group, else the largest group that tiles the row."""
    return min(host.GROUP_WORDS, width)


def encode_rows(rows, *, width: Optional[int] = None,
                backend: str = "pallas", interpret: bool = False):
    """Encode uint32 device ``rows`` [R, W] (R >= 1, W a power of two >=
    MIN_GROUP_WORDS); rows of another shape (such as ``delta_pack``'s word
    tiles [R, ..]) are read as their first ``width`` words each.

    Returns (masks np.uint32 [R*gpr, 2], planes_dev [n_stored, gw//32]
    still on device, gw).  Only the masks (8 bytes/group) are materialized
    here; the caller transfers ``planes_dev`` when it is ready for it."""
    import jax.numpy as jnp

    r = int(rows.shape[0])
    w = int(width or rows.shape[1])
    if rows.ndim != 2 or rows.shape[1] != w:
        rows = rows.reshape(r, -1)[:, :w]
    gw = group_words_for(w)
    if gw < host.MIN_GROUP_WORDS or w % gw:
        raise ValueError(f"row width {w} not codec-eligible")
    gpr = w // gw
    rp = max(_MIN_ROW_PAD, host.pow2ceil(r))
    if rp > r:                          # pad: bounded jit shape universe
        rows = jnp.zeros((rp, w), jnp.uint32).at[:r].set(rows)
    if backend == "pallas":
        from repro.kernels.delta_codec.kernel import codec_encode_pallas
        masks_d, _count, planes_d = codec_encode_pallas(
            rows, gw=gw, interpret=interpret)
    elif backend == "ref":
        from repro.kernels.delta_codec.ref import codec_encode_ref
        masks_d, _count, planes_d = codec_encode_ref(rows, gw=gw)
    else:
        raise ValueError(f"unknown codec backend {backend!r}")
    with obs.span("d2h", bytes=int(masks_d.nbytes)):
        masks = np.asarray(masks_d)[: r * gpr].astype("<u4")
    obs.count("kishu_d2h_bytes_total", masks_d.nbytes, path="codec")
    obs.count("kishu_host_syncs_total", where="read_chunks")
    n_stored = int(host.popcount_u32(masks[:, 0]).sum())
    return masks, planes_d[:n_stored], gw


def probe_device_rows(rows, max_rows: int = 4,
                      sample_words: int = 256) -> bool:
    """Device-side analogue of ``host.bitplane_probe``: pull a small word
    sample from the compacted buffer (a few hundred bytes over PCIe) and
    estimate whether the encode is worth launching at all."""
    r = int(rows.shape[0])
    if r == 0:
        return False
    take = min(r, max_rows)
    w = int(np.prod(rows.shape[1:]))
    step = max(1, (take * w) // sample_words)
    sample = np.asarray(rows[:take].reshape(-1)[::step][:sample_words])
    obs.count("kishu_d2h_bytes_total", sample.nbytes, path="codec")
    obs.count("kishu_host_syncs_total", where="read_chunks")
    return host.estimate_stored_fraction(sample) < host.PROBE_THRESHOLD
