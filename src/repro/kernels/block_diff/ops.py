"""jit'd public wrapper for exact per-chunk diffing of two same-shape arrays."""
from __future__ import annotations

import functools
from typing import Literal

import jax
import numpy as np

from repro.kernels.block_diff.kernel import block_diff_pallas
from repro.kernels.block_diff.ref import block_diff_ref
from repro.kernels.chunk_hash.ops import chunk_rows
from repro.kernels.common import platform_backend


@functools.partial(jax.jit,
                   static_argnames=("chunk_bytes", "backend", "interpret"))
def block_diff(a: jax.Array, b: jax.Array, chunk_bytes: int = 1 << 18, *,
               backend: Literal["pallas", "ref"] = "pallas",
               interpret: bool = False) -> jax.Array:
    """int32 [n_chunks]: 1 iff chunk i of a and b differ bitwise.

    a and b must have identical shape/dtype (structure changes are detected
    before content compare — covariable.py).
    """
    assert a.shape == b.shape and a.dtype == b.dtype, "structure mismatch"
    assert chunk_bytes % 4 == 0 and chunk_bytes & (chunk_bytes - 1) == 0
    wa, wb = chunk_rows(a, chunk_bytes), chunk_rows(b, chunk_bytes)
    if backend == "pallas":
        return block_diff_pallas(wa, wb, interpret=interpret)
    return block_diff_ref(wa, wb)


def dirty_chunks(a: jax.Array, b: jax.Array,
                 chunk_bytes: int = 1 << 18) -> np.ndarray:
    """Indices of chunks where ``a`` and ``b`` differ bitwise, as a host
    int array — the exact-compare entry point the delta pipeline wires in
    (the Pallas kernel on a TPU, the jnp reference elsewhere)."""
    mask = block_diff(a, b, chunk_bytes, backend=platform_backend(a))
    return np.nonzero(np.asarray(mask))[0]
