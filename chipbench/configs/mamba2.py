"""Plain reference of the Mamba-2 language model (arXiv:2405.21060, the SSD
layer of ``mamba_ssm``'s ``Mamba2``), as the training cells run it.

Each block: RMSNorm, one input projection to (z, x, B, C, dt), a causal
depthwise convolution with SiLU over (x, B, C), the SSD recurrence in its
quadratic (attention-like) form over the whole sequence, a skip ``D * x``,
a gated RMSNorm ``norm(y * silu(z))``, and the output projection; a final
RMSNorm and an output head tied to the embedding.  Matrix parameters are
bfloat16; ``A_log``, ``D`` and ``dt_bias`` are float32 as in the published
code.  The quadratic form computes the same outputs as the chunked scan at
these sequence lengths.  Departures: none in the equations; the layers held
are the chip's share, as the configuration file states.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

PARAM_DTYPE = jnp.bfloat16


def _dims(cfg: dict):
    d = cfg["d_model"]
    ssm = cfg["ssm_cfg"]
    d_in = ssm["expand"] * d
    heads = d_in // ssm["headdim"]
    gn = ssm["ngroups"] * ssm["d_state"]
    return d, d_in, heads, gn, ssm


def vocab_rows(cfg: dict) -> int:
    """Rows of the embedding and the tied head: the token ids padded to a
    multiple of ``pad_vocab_size_multiple``, as ``mamba_ssm`` pads them."""
    m = cfg.get("pad_vocab_size_multiple", 1)
    return -(-cfg["vocab_size"] // m) * m


def init_params(cfg: dict, key) -> Dict:
    d, d_in, heads, gn, ssm = _dims(cfg)
    conv_ch = d_in + 2 * gn
    ks = iter(jax.random.split(key, 8 * cfg["n_layer"] + 2))

    def dense(shape, scale=0.02):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(PARAM_DTYPE)

    layers = {}
    for i in range(cfg["n_layer"]):
        dt = jnp.exp(jax.random.uniform(next(ks), (heads,), jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        layers[str(i)] = {
            "norm": jnp.ones((d,), PARAM_DTYPE),
            "in_proj": dense((d, 2 * d_in + 2 * gn + heads)),
            "conv_w": dense((ssm["d_conv"], conv_ch),
                            1.0 / math.sqrt(ssm["d_conv"])),
            "conv_b": jnp.zeros((conv_ch,), PARAM_DTYPE),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # inverse softplus
            "A_log": jnp.log(jax.random.uniform(next(ks), (heads,),
                                                jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "gate_norm": jnp.ones((d_in,), PARAM_DTYPE),
            "out_proj": dense((d_in, d)),
        }
    return {"embed": dense((vocab_rows(cfg), d)), "layers": layers,
            "final_norm": jnp.ones((d,), PARAM_DTYPE)}


def _rms(x, w, eps=1e-5):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _mm(x, w):
    return jnp.dot(x.astype(PARAM_DTYPE), w,
                   preferred_element_type=jnp.float32)


def _block(p, h, cfg):
    d, d_in, heads, gn, ssm = _dims(cfg)
    b, s, _ = h.shape
    zxbcdt = _mm(_rms(h, p["norm"]), p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * gn], axis=-1)
    w = p["conv_w"].astype(jnp.float32)
    pad = jnp.pad(xbc, ((0, 0), (w.shape[0] - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + s] * w[i] for i in range(w.shape[0]))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    x, bm, cm = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
    x = x.reshape(b, s, heads, ssm["headdim"])
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # [b, s, H]
    a = -jnp.exp(p["A_log"])                                 # [H]
    cum = jnp.cumsum(dt * a, axis=1)                         # [b, s, H]
    seg = cum[:, :, None, :] - cum[:, None, :, :]            # [b, t, s, H]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    scores = jnp.einsum("btn,bsn->bts", cm, bm)              # one group
    wts = scores[..., None] * decay * dt[:, None, :, :]      # [b, t, s, H]
    y = jnp.einsum("btsh,bshp->bthp", wts, x)
    y = y + p["D"][None, None, :, None] * x
    y = y.reshape(b, s, d_in) * jax.nn.silu(z)
    y = _rms(y, p["gate_norm"])
    return h + _mm(y, p["out_proj"])


def loss(params, tokens, cfg: dict):
    """Mean next-token cross-entropy over ``tokens`` [B, S+1]."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    h = params["embed"][inp].astype(jnp.float32)
    for i in range(cfg["n_layer"]):
        h = _block(params["layers"][str(i)], h, cfg)
    h = _rms(h, params["final_norm"])
    logits = jnp.dot(h.astype(PARAM_DTYPE), params["embed"].T,
                     preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def matmul_params(cfg: dict) -> int:
    d, d_in, heads, gn, _ = _dims(cfg)
    per_layer = d * (2 * d_in + 2 * gn + heads) + d_in * d
    return cfg["n_layer"] * per_layer + vocab_rows(cfg) * d


def attn_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward operations per token of the SSD score and value products in
    the quadratic form (causal half)."""
    d, d_in, heads, gn, ssm = _dims(cfg)
    return cfg["n_layer"] * (2 * seq * gn + 2 * seq * d_in) / 2
