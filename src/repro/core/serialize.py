"""Leaf serialization for session states.

Exact, dtype-preserving byte views — no pickle for arrays, so roundtrips are
bit-exact by construction (the paper's "silent pickling errors" class cannot
occur for arrays; it is *simulated* via :class:`OpaqueLeaf` to exercise
fallback recomputation, mirroring generators/locks/remote handles in §5.1).

A leaf is one of:
  - ``jax.Array`` / ``np.ndarray``  -> raw bytes + (dtype, shape[, strides]) meta
  - jax typed PRNG key              -> key-data uint32 bytes + impl tag
  - small python objects            -> pickled (scalars, tuples, strs)
  - ``OpaqueLeaf``                  -> SerializationError (unserializable)
"""
from __future__ import annotations

import pickle
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


class SerializationError(Exception):
    """Raised when a leaf cannot be serialized (paper §5.1: skip storage,
    fall back to recomputation at checkout)."""


class ChunkMissingError(Exception):
    """A chunk referenced by a manifest is absent/corrupt in the store."""


@dataclass
class OpaqueLeaf:
    """Simulates an unserializable object (generator, lock, GPU ipc handle).

    Carries a payload so fallback recomputation can be *verified* to rebuild
    the correct value; serialization of the leaf itself always fails.
    """
    payload: Any = None
    note: str = "unserializable"

    def __reduce__(self):
        raise SerializationError(f"OpaqueLeaf({self.note}) cannot be pickled")

    def __eq__(self, other):
        return isinstance(other, OpaqueLeaf) and other.payload == self.payload \
            and other.note == self.note


def is_array_leaf(x: Any) -> bool:
    return isinstance(x, (np.ndarray, jax.Array))


def is_prng_key(x: Any) -> bool:
    return isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jax.dtypes.prng_key)


def base_of(x: Any) -> Any:
    """Ultimate base buffer of a (possibly viewed) array leaf."""
    if isinstance(x, np.ndarray):
        while isinstance(x.base, np.ndarray):
            x = x.base
        return x
    return x


def view_spec(x: Any, base: Any) -> Optional[dict]:
    """(offset, shape, strides, dtype) of x relative to base, or None if
    x *is* the base."""
    if x is base:
        return None
    assert isinstance(x, np.ndarray) and isinstance(base, np.ndarray)
    off = x.__array_interface__["data"][0] - base.__array_interface__["data"][0]
    return {"offset": int(off), "shape": list(x.shape),
            "strides": list(x.strides), "dtype": str(x.dtype)}


def leaf_meta(x: Any) -> dict:
    if is_prng_key(x):
        data = jax.random.key_data(x)
        return {"kind": "prng", "impl": str(jax.random.key_impl(x)),
                "dtype": str(data.dtype), "shape": list(data.shape)}
    if is_array_leaf(x):
        dt = np.dtype(x.dtype)
        meta = {"kind": "array", "dtype": str(dt),
                "shape": list(x.shape), "jax": isinstance(x, jax.Array)}
        if dt.fields:                       # structured dtype: store descr
            meta["dtype_descr"] = [list(d) for d in dt.descr]
        return meta
    return {"kind": "object", "type": type(x).__name__}


def _to_host(x: Any) -> np.ndarray:
    """``np.asarray(x)``; for a device array, the counted, spanned
    device→host copy of the whole leaf."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    nbytes = int(x.size) * np.dtype(x.dtype).itemsize
    with obs.span("d2h", bytes=nbytes):
        arr = np.asarray(x)
    obs.count("kishu_d2h_bytes_total", arr.nbytes, path="leaf")
    obs.count("kishu_host_syncs_total", where="leaf")
    return arr


def _to_device(arr: np.ndarray) -> jax.Array:
    """``jnp.asarray(arr)``: the counted, spanned host→device upload of a
    loaded leaf."""
    with obs.span("h2d", bytes=int(arr.nbytes)):
        out = jnp.asarray(arr)
    obs.count("kishu_h2d_bytes_total", arr.nbytes, path="load")
    return out


def leaf_buffer(x: Any) -> Tuple[Union[memoryview, bytes], dict]:
    """Serialize a *base* leaf without copying its bytes: an array or PRNG
    key is a flat byte view of its C-order host copy (a numpy leaf's own
    buffer, so a chunk kept past the call must be copied out), anything
    else pickled.  Raises SerializationError for opaque leaves."""
    meta = leaf_meta(x)
    if meta["kind"] in ("prng", "array"):
        arr = _to_host(jax.random.key_data(x) if meta["kind"] == "prng"
                       else x)
        if not arr.flags["C_CONTIGUOUS"]:
            arr = np.ascontiguousarray(arr)
        if arr.dtype.hasobject:         # no byte view of object pointers
            return arr.tobytes(), meta
        return memoryview(arr.reshape(-1).view(np.uint8)), meta
    if isinstance(x, OpaqueLeaf):
        raise SerializationError(f"OpaqueLeaf({x.note})")
    try:
        return pickle.dumps(x), meta
    except Exception as e:  # noqa: BLE001 — any pickling failure is EAFP
        raise SerializationError(str(e)) from e


def leaf_to_bytes(x: Any) -> Tuple[bytes, dict]:
    """Serialize a *base* leaf. Raises SerializationError for opaque leaves."""
    buf, meta = leaf_buffer(x)
    return bytes(buf), meta


def leaf_from_bytes(data: bytes, meta: dict, *, device_put: bool = True) -> Any:
    if meta["kind"] == "prng":
        raw = np.frombuffer(data, dtype=np.dtype(meta["dtype"])) \
            .reshape(meta["shape"]).copy()
        return jax.random.wrap_key_data(_to_device(raw))
    if meta["kind"] == "array":
        if meta.get("dtype_descr"):
            dt = np.dtype([tuple(d) for d in meta["dtype_descr"]])
        else:
            dt = np.dtype(meta["dtype"])
        arr = np.frombuffer(data, dtype=dt).reshape(meta["shape"]).copy()
        if meta.get("jax") and device_put:
            return _to_device(arr)
        return arr
    return pickle.loads(data)


def view_from_base(base: np.ndarray, spec: dict) -> np.ndarray:
    """Reconstruct a strided view into ``base`` (shared-reference restore)."""
    flat = base.reshape(-1).view(np.uint8)
    dt = np.dtype(spec["dtype"])
    return np.lib.stride_tricks.as_strided(
        flat[spec["offset"]:].view(dt),
        shape=tuple(spec["shape"]), strides=tuple(spec["strides"]))


def leaf_nbytes(x: Any) -> int:
    if is_prng_key(x):
        return int(np.asarray(jax.random.key_data(x)).nbytes)
    if is_array_leaf(x):
        return int(np.dtype(x.dtype).itemsize
                   * int(np.prod(x.shape, dtype=np.int64)))
    try:
        return len(pickle.dumps(x))
    except Exception:  # noqa: BLE001
        return 0
