"""Peaks of each chip, and the operations and bytes each piece of work needs.

The table is keyed by ``device_kind`` as JAX reports it.  A kind that is not
in the table is an error: a share of a peak is never computed against a
guess.

Source of the TPU v5e row: Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM
per chip.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Peak:
    flops_bf16: float          # operations per second
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


_V5E = Peak(flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 10**9,
            source='Google Cloud documentation, "TPU v5e"')

PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": _V5E,         # the kind JAX reports for a v5e chip
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak is known for device kind {device_kind!r}; "
                       f"add its row to chipbench/peaks.py with its source"
                       ) from None


def least_time_s(peak: Peak, *, flops: float = 0.0,
                 hbm_bytes: float = 0.0) -> float:
    """The least time the chip needs: the larger of the compute bound and
    the memory bound."""
    return max(flops / peak.flops_bf16, hbm_bytes / peak.hbm_bytes_per_s)


# ---------------------------------------------------------------------------
# bytes per kernel call, from shapes
# ---------------------------------------------------------------------------

def delta_pack_bytes(nbytes: int, chunk_bytes: int, dirty_chunks: int) -> int:
    """Least HBM traffic of one fused hash + diff + compaction pass over an
    array of ``nbytes``: the array read once, 12 bytes per chunk of hashes
    and flags in and out, and the dirty chunks written once to the
    compacted buffer."""
    n_chunks = -(-nbytes // chunk_bytes)
    return nbytes + 24 * n_chunks + dirty_chunks * chunk_bytes


def patch_scatter_bytes(rows: int, chunk_bytes: int) -> int:
    """Least HBM traffic of landing ``rows`` dirty chunks: each read once
    from the uploaded buffer and written once into the live array (the
    kernel's power-of-two padding rows are not needed work)."""
    return 2 * rows * chunk_bytes


# ---------------------------------------------------------------------------
# operations and bytes per traffic cell, from the configuration
# ---------------------------------------------------------------------------

def train_step_flops(param_counts: Dict[str, int], tokens: int,
                     attn_flops_per_token: float = 0.0) -> float:
    """Forward and backward operations of one training step: 6 per
    parameter per token for every matrix the token passes through, plus the
    attention score and value products.  ``param_counts['matmul']`` counts
    the parameters used as matrices (the tied output head included; the
    embedding gather is not a matrix product)."""
    return 6.0 * param_counts["matmul"] * tokens \
        + 3.0 * attn_flops_per_token * tokens
