"""Leaf-type compatibility matrix (the paper's Fig. 12 / Tables 4-5).

For every leaf type: checkpoint -> mutate -> checkout must restore the
mutated value bit-exactly with its type, the mutation must be detected as
an update, and in a fresh session a read-only access must not be flagged
as one — except for
opaque leaves, whose semantics the session cannot see, so any access
counts as an update (a false positive, never a missed change).
"""
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KishuSession, MemoryStore, OpaqueLeaf


def _jnp(dtype):
    return lambda: jnp.arange(64, dtype=dtype)


LEAF_TYPES: Dict[str, Callable[[], Any]] = {
    "np.float32": lambda: np.arange(64, dtype=np.float32),
    "np.float64": lambda: np.arange(64, dtype=np.float64),
    "np.float16": lambda: np.arange(64, dtype=np.float16),
    "np.int8": lambda: np.arange(64, dtype=np.int8),
    "np.int16": lambda: np.arange(64, dtype=np.int16),
    "np.int32": lambda: np.arange(64, dtype=np.int32),
    "np.int64": lambda: np.arange(64, dtype=np.int64),
    "np.uint8": lambda: np.arange(64, dtype=np.uint8),
    "np.bool": lambda: np.arange(64) % 2 == 0,
    "np.complex64": lambda: (np.arange(64) + 1j).astype(np.complex64),
    "np.structured": lambda: np.zeros(8, dtype=[("a", "f4"), ("b", "i4")]),
    "np.view_slice": lambda: np.arange(100, dtype=np.float32)[10:50],
    "np.view_strided": lambda: np.arange(100, dtype=np.float32)[::2],
    "np.scalar0d": lambda: np.array(3.5, np.float32),
    "jax.float32": _jnp(jnp.float32),
    "jax.bfloat16": _jnp(jnp.bfloat16),
    "jax.float16": _jnp(jnp.float16),
    "jax.int32": _jnp(jnp.int32),
    "jax.int8": _jnp(jnp.int8),
    "jax.uint32": _jnp(jnp.uint32),
    "jax.bool": lambda: jnp.arange(64) % 2 == 0,
    "jax.prng_key": lambda: jax.random.key_data(jax.random.key(7)),
    "jax.prng_typed": lambda: jax.random.key(7),
    "py.int": lambda: 41,
    "py.float": lambda: 2.5,
    "py.str": lambda: "hello",
    "py.bytes": lambda: b"\x00\x01\x02",
    "py.list": lambda: [1, 2, 3],
    "py.dict": lambda: {"a": 1},
    "py.tuple_nested": lambda: (1, (2, [3, 4])),
    "py.none": lambda: None,
    # generator / lock / remote-handle analogues
    "opaque.handle": lambda: OpaqueLeaf(payload=1, note="generator"),
    "opaque.remote": lambda: OpaqueLeaf(payload="ray://ds", note="remote ds"),
}


def _mutate(v: Any) -> Any:
    if isinstance(v, OpaqueLeaf):
        return OpaqueLeaf(payload=(v.payload, "mut"), note=v.note)
    if isinstance(v, np.ndarray):
        if v.dtype.fields:
            out = v.copy()
            out["a"] = out["a"] + 1
            return out
        if v.ndim == 0:
            return np.array(v + 1, v.dtype)      # keep the 0-d ndarray type
        return v + v.dtype.type(1) if v.dtype != bool else ~v
    if isinstance(v, jax.Array):
        if jnp.issubdtype(v.dtype, jax.dtypes.prng_key):
            return jax.random.split(v, 1)[0]
        return ~v if v.dtype == jnp.bool_ else v + 1
    if isinstance(v, (int, float)):
        return v + 1
    if isinstance(v, str):
        return v + "!"
    if isinstance(v, bytes):
        return v + b"!"
    if isinstance(v, list):
        return v + [9]
    if isinstance(v, dict):
        return {**v, "z": 9}
    if isinstance(v, tuple):
        return v + (9,)
    if v is None:
        return ()
    raise TypeError(type(v))


def _equal(a: Any, b: Any) -> bool:
    if isinstance(a, jax.Array) and jnp.issubdtype(a.dtype,
                                                   jax.dtypes.prng_key):
        return (isinstance(b, jax.Array) and b.dtype == a.dtype
                and bool(jnp.all(jax.random.key_data(a)
                                 == jax.random.key_data(b))))
    if isinstance(a, (np.ndarray, jax.Array)):
        return isinstance(b, jax.Array) == isinstance(a, jax.Array) \
            and np.asarray(a).dtype == np.asarray(b).dtype \
            and np.array_equal(np.asarray(a), np.asarray(b))
    return type(a) is type(b) and a == b


def _touches_x(sess, cid) -> bool:
    return any("x" in tuple(k) for k in sess.graph.nodes[cid].manifests)


@pytest.mark.parametrize("name", sorted(LEAF_TYPES))
def test_leaf_type_roundtrip(name):
    make = LEAF_TYPES[name]

    def seed(ns):
        ns["x"] = make()                 # a dict leaf stays one leaf

    def mutate(ns):
        ns["x"] = _mutate(ns["x"])

    def read_only(ns):
        _ = ns["x"]
        ns["probe"] = ns["probe"] + 1 if "probe" in ns.base else 1

    def session():
        sess = KishuSession(MemoryStore(), chunk_bytes=1 << 12)
        for fn in (seed, mutate, read_only):
            sess.register(fn.__name__, fn)
        sess.init_state({})
        sess.run("seed")
        return sess

    sess = session()
    cid = sess.run("mutate")
    assert _touches_x(sess, cid), "update not detected"
    sess.run("mutate")
    sess.checkout(cid)
    assert _equal(sess.ns["x"], _mutate(make())), "restore not exact"
    sess.close()

    sess = session()
    probe = sess.run("read_only")
    assert _touches_x(sess, probe) == name.startswith("opaque.")
    sess.close()
