"""Pallas TPU flash-attention (forward): tiled online-softmax attention.

Addresses the dominant roofline term found in §Perf cell A: naive attention
materializes S² logits to HBM; this kernel keeps the [BQ, BK] score tile and
the [BQ, hd] accumulator in VMEM, streaming K/V blocks — HBM traffic drops
from O(S²·H) to O(S·hd·H·S/BK) (the K/V re-reads), a ~BK/3 reduction.

Grid: (B·Hq, S/BQ, S/BK) with the K dimension innermost; running max /
normalizer / accumulator live in VMEM scratch across K iterations
(initialized at ik==0, output written at the last K block).  Causal blocks
strictly above the diagonal are skipped via pl.when; partial blocks mask in
f32 with -1e30 (finite: avoids -inf NaN propagation through the online
update).  GQA is handled in the K/V index maps (query-head -> kv-head), so
KV blocks are never materially repeated.

VMEM budget at BQ=BK=512, hd<=256: scores 1 MB f32 + q/k/v tiles ~0.8 MB
+ acc 0.5 MB — comfortably inside 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _update():
        q = q_ref[0].astype(jnp.float32)              # [BQ, hd]
        k = k_ref[0].astype(jnp.float32)              # [BK, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = qpos >= kpos
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_prev + jnp.sum(p, axis=1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip K blocks strictly above the causal diagonal
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_update)
    else:
        _update()

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "n_rep", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         block_q: int = 512, block_k: int = 512,
                         n_rep: int = 1, interpret: bool = False):
    """q: [BHq, S, hd]; k,v: [BHkv, S, hd] with BHq = BHkv * n_rep.
    Returns [BHq, S, hd]."""
    bh, s, hd = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_q = s // block_q
    n_k = s // block_k
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, iq, ik, _r=n_rep: (b // _r, ik, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, iq, ik, _r=n_rep: (b // _r, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            _vmem((block_q,), jnp.float32),       # running max
            _vmem((block_q,), jnp.float32),       # running normalizer
            _vmem((block_q, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        name="flash_attention_pallas",
    )(q, k, v)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
