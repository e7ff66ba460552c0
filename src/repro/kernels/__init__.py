"""Pallas TPU kernels.

Paper hot-spots (delta detection — §6.2's hash-based detection made the
primary mechanism in the TPU adaptation):

- ``chunk_hash``: per-chunk detection hashing at HBM bandwidth.
- ``block_diff``: exact per-chunk dirty-compare when both versions are
  device-resident (undo fast path).

Beyond-paper:

- ``flash_attention``: tiled online-softmax attention (forward/prefill) —
  removes the S²-logit HBM traffic that dominates the roofline memory term
  for long-sequence cells.

Each kernel ships kernel.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd public wrapper) and ref.py (pure-jnp oracle); tests
sweep shapes/dtypes and assert agreement in interpret mode.
"""
from repro.kernels.chunk_hash import chunk_hash, chunk_hash_u64
from repro.kernels.block_diff import block_diff
from repro.kernels.flash_attention import flash_attention

__all__ = ["chunk_hash", "chunk_hash_u64", "block_diff", "flash_attention"]
