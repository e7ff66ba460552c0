"""Plain reference of the Qwen3 decoder (arXiv:2505.09388; the published
``config.json`` of each size), as the training cells run it.

Pre-norm blocks: RMSNorm, grouped-query attention with RMSNorm on each
query and key head (qk-norm) and rotary positions, RMSNorm, SwiGLU MLP; a
final RMSNorm and an output head tied to the embedding.  Parameters are
bfloat16; every matrix product accumulates in float32.  Departures from the
published model: none in the equations; the layers held and the vocabulary
rows are the chip's share, as the configuration file states.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

PARAM_DTYPE = jnp.bfloat16


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"])


def init_params(cfg: dict, key) -> Dict:
    d, h, kv, hd, f = _dims(cfg)
    ks = iter(jax.random.split(key, 8 * cfg["num_hidden_layers"] + 2))

    def dense(shape, scale=0.02):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(PARAM_DTYPE)

    ones = lambda n: jnp.ones((n,), PARAM_DTYPE)
    layers = {}
    for i in range(cfg["num_hidden_layers"]):
        layers[str(i)] = {
            "attn_norm": ones(d), "wq": dense((d, h * hd)),
            "wk": dense((d, kv * hd)), "wv": dense((d, kv * hd)),
            "wo": dense((h * hd, d)), "q_norm": ones(hd),
            "k_norm": ones(hd), "mlp_norm": ones(d),
            "w_gate": dense((d, f)), "w_up": dense((d, f)),
            "w_down": dense((f, d)),
        }
    return {"embed": dense((cfg["vocab_size"], d)), "layers": layers,
            "final_norm": ones(d)}


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _mm(x, w):
    return jnp.dot(x.astype(PARAM_DTYPE), w,
                   preferred_element_type=jnp.float32)


def _rope(x, theta):
    """x: [B, S, H, hd] float32; rotate-half convention."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _block(p, x, cfg):
    d, h, kv, hd, _ = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    b, s, _ = x.shape
    u = _rms(x, p["attn_norm"], eps)
    q = _mm(u, p["wq"]).reshape(b, s, h, hd)
    k = _mm(u, p["wk"]).reshape(b, s, kv, hd)
    v = _mm(u, p["wv"]).reshape(b, s, kv, hd)
    q = _rope(_rms(q, p["q_norm"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, p["k_norm"], eps), cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(PARAM_DTYPE),
                    k.astype(PARAM_DTYPE),
                    preferred_element_type=jnp.float32) / jnp.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, -1e30)
    pr = jax.nn.softmax(sc, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr.astype(PARAM_DTYPE),
                   v.astype(PARAM_DTYPE), preferred_element_type=jnp.float32)
    x = x + _mm(o.reshape(b, s, h * hd), p["wo"])
    u = _rms(x, p["mlp_norm"], eps)
    g = jax.nn.silu(_mm(u, p["w_gate"])) * _mm(u, p["w_up"])
    return x + _mm(g, p["w_down"])


def loss(params, tokens, cfg: dict):
    """Mean next-token cross-entropy over ``tokens`` [B, S+1] (ids in the
    vocabulary held here)."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inp].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(params["layers"][str(i)], x, cfg)
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = jnp.dot(x.astype(PARAM_DTYPE), params["embed"].T,
                     preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def matmul_params(cfg: dict) -> int:
    """Parameters a token passes through as matrices (output head
    included)."""
    d, h, kv, hd, f = _dims(cfg)
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def attn_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward operations per token of the score and value products over
    the whole (causal) sequence."""
    _, h, _, hd, _ = _dims(cfg)
    return cfg["num_hidden_layers"] * 2 * 2 * seq * h * hd / 2
