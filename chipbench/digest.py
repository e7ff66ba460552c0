"""Fingerprints of a session's state, for the comparison that decides
``correct``.

A fingerprint maps every name to its kind, dtype and shape and two 32-bit
position-weighted sums of its words, computed on the device in one jitted
call.  Each weight is odd, so a change of a single word always changes both
sums; python values are kept as their ``repr``.  Whether the tied pair still
shares one array object is part of the fingerprint.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

TIED = ("state/params/embed", "state/params/lm_head")

_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _words(x) -> jax.Array:
    """The array's bits, one uint32 word per element."""
    item = np.dtype(x.dtype).itemsize
    return jax.lax.bitcast_convert_type(x, _UINT[item]).astype(
        jnp.uint32).reshape(-1)


def _leaf_sums(x) -> jax.Array:
    u = _words(x)
    i = jnp.arange(u.shape[0], dtype=jnp.uint32)
    w1 = i * jnp.uint32(2) + jnp.uint32(1)
    w2 = (i * jnp.uint32(0x9E3779B1)) | jnp.uint32(1)
    mix = u ^ (u >> jnp.uint32(15)) ^ jnp.uint32(0x85EBCA6B)
    return jnp.stack([jnp.sum(u * w1, dtype=jnp.uint32),
                      jnp.sum(mix * w2, dtype=jnp.uint32)])


@jax.jit
def _sums(leaves: List[jax.Array]) -> jax.Array:
    return jnp.stack([_leaf_sums(x) for x in leaves])


def fingerprint(ns) -> Dict[str, Any]:
    """The fingerprint of a namespace (a mapping of names to leaves)."""
    names = sorted(ns.keys())
    arrays = [n for n in names if isinstance(ns[n], jax.Array)]
    out: Dict[str, Any] = {}
    if arrays:
        sums = np.asarray(_sums([ns[n] for n in arrays]))
        for n, s in zip(arrays, sums):
            x = ns[n]
            out[n] = ("array", str(x.dtype), tuple(x.shape),
                      int(s[0]), int(s[1]))
    for n in names:
        if n not in out:
            v = ns[n]
            out[n] = ("value", type(v).__name__, repr(v))
    if all(t in ns for t in TIED):
        out["<tied>"] = ns[TIED[0]] is ns[TIED[1]]
    return out


def differences(got: Dict[str, Any], want: Dict[str, Any]) -> List[str]:
    """Names whose entry differs, sorted."""
    return sorted(n for n in set(got) | set(want) if got.get(n) != want.get(n))
