"""jit'd public wrapper: [B,S,H,hd] GQA flash attention (forward/prefill).

Handles layout ([B,S,H,hd] <-> [B*H,S,hd]), GQA head-group mapping via the
kernel's K/V index maps (no materialized repeat), and the ref dispatch.
Forward-only: the training path keeps XLA attention (a Pallas backward is
future work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "backend", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512, backend: str = "pallas",
                    interpret: bool = False) -> jax.Array:
    """q: [B,S,Hq,hd]; k,v: [B,S,Hkv,hd] (Hq % Hkv == 0) -> [B,S,Hq,hd]."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    n_rep = hq // hkv
    if backend == "ref":
        if n_rep > 1:
            k = jnp.repeat(k, n_rep, axis=2)
            v = jnp.repeat(v, n_rep, axis=2)
        return flash_attention_ref(q, k, v, causal=causal)

    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, hd)
    of = flash_attention_bhsd(qf, kf, vf, causal=causal, block_q=block_q,
                              block_k=block_k, n_rep=n_rep,
                              interpret=interpret)
    return of.reshape(b, hq, s, hd).transpose(0, 2, 1, 3)
