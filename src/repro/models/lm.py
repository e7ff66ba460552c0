"""Unified language model covering all 10 assigned architectures.

One config-driven decoder (+ optional encoder for enc-dec) built from:
  - per-layer specs (attention kind x FFN kind) derived from ArchConfig
  - scan-over-layers with stacked parameters, grouped into *stages* of
    repeating units so heterogeneous stacks (hybrid interleave, dense-prefix
    MoE) still lower to compact HLO
  - remat (jax.checkpoint) around the unit body for training
  - full-sequence forward (train/prefill) and one-token decode with caches

Parameters are nested dicts of arrays; caches are nested dicts stacked along
a leading n_units dim per stage so decode also scans.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models import layers, mamba, moe as moe_lib

Array = jax.Array


# ---------------------------------------------------------------------------
# layer specs and stages
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerSpec:
    kind: str          # "attn" | "ssm"
    ffn: str           # "dense" | "moe" | "none"
    cross: bool = False  # decoder cross-attention (enc-dec)


@dataclass(frozen=True)
class StageSpec:
    unit: Tuple[LayerSpec, ...]
    n_units: int


def layer_specs(cfg: ArchConfig, *, decoder: bool = True) -> List[LayerSpec]:
    kinds = cfg.layer_kinds
    specs = []
    for i, kind in enumerate(kinds):
        if cfg.family == "ssm":
            ffn = "none"
        elif cfg.moe is not None and i >= cfg.moe.n_dense_layers and \
                (i % cfg.moe.every_k_layers == cfg.moe.every_k_layers - 1):
            ffn = "moe"
        else:
            ffn = "dense"
        specs.append(LayerSpec(kind, ffn, cross=cfg.enc_dec and decoder))
    return specs


def _min_period(specs: List[LayerSpec]) -> int:
    n = len(specs)
    for u in range(1, n + 1):
        if n % u == 0 and all(specs[i] == specs[i % u] for i in range(n)):
            return u
    return n


def build_stages(cfg: ArchConfig, *, decoder: bool = True) -> List[StageSpec]:
    """Split the layer stack into (prefix) + (periodic) stages."""
    specs = layer_specs(cfg, decoder=decoder)
    prefix = cfg.moe.n_dense_layers if cfg.moe else 0
    stages: List[StageSpec] = []
    if prefix:
        head = specs[:prefix]
        u = _min_period(head)
        stages.append(StageSpec(tuple(head[:u]), len(head) // u))
        specs = specs[prefix:]
    if specs:
        u = _min_period(specs)
        stages.append(StageSpec(tuple(specs[:u]), len(specs) // u))
    return stages


def encoder_stages(cfg: ArchConfig) -> List[StageSpec]:
    n_enc = cfg.n_encoder_layers or cfg.n_layers
    spec = LayerSpec("attn", "dense", cross=False)
    return [StageSpec((spec,), n_enc)]


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, spec: LayerSpec, dtype) -> dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": layers.rmsnorm_init(d, dtype)}
    if spec.kind == "attn":
        if cfg.mla is not None:
            p["attn"] = layers.mla_init(ks[0], cfg, dtype)
        else:
            p["attn"] = layers.gqa_init(ks[0], cfg, dtype)
    else:
        p["ssm"] = mamba.ssm_init(ks[0], cfg, dtype)
    if spec.cross:
        p["cross_norm"] = layers.rmsnorm_init(d, dtype)
        p["cross"] = layers.cross_attn_init(ks[1], cfg, dtype)
    if spec.ffn == "dense":
        p["norm2"] = layers.rmsnorm_init(d, dtype)
        p["mlp"] = layers.mlp_init(ks[2], d, cfg.d_ff, dtype)
    elif spec.ffn == "moe":
        p["norm2"] = layers.rmsnorm_init(d, dtype)
        p["moe"] = moe_lib.moe_init(ks[2], cfg, dtype)
    return p


def _init_stage(key, cfg: ArchConfig, stage: StageSpec, dtype) -> dict:
    def unit_init(k):
        uks = jax.random.split(k, len(stage.unit))
        return {f"sub_{j}": _init_layer(uks[j], cfg, spec, dtype)
                for j, spec in enumerate(stage.unit)}
    keys = jax.random.split(key, stage.n_units)
    return jax.vmap(unit_init)(keys)


def init_params(cfg: ArchConfig, key: Array, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    embed = (jax.random.normal(ks[0], (cfg.padded_vocab, d), jnp.float32)
             * 0.02).astype(dtype)
    params: Dict[str, Any] = {
        "embed": embed,
        "final_norm": layers.rmsnorm_init(d, dtype),
        "stages": {},
    }
    for i, stage in enumerate(build_stages(cfg)):
        params["stages"][f"stage_{i}"] = _init_stage(ks[1 + i % 4], cfg, stage, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_param(ks[5], d, cfg.padded_vocab, dtype)
    if cfg.enc_dec:
        enc: Dict[str, Any] = {"final_norm": layers.rmsnorm_init(d, dtype),
                               "stages": {}}
        for i, stage in enumerate(encoder_stages(cfg)):
            enc["stages"][f"stage_{i}"] = _init_stage(ks[6], cfg, stage, dtype)
        params["encoder"] = enc
    if cfg.mtp:
        params["mtp"] = {
            "proj": layers.dense_param(ks[7], 2 * d, d, dtype),
            "norm": layers.rmsnorm_init(d, dtype),
            "block": _init_layer(ks[3], cfg, LayerSpec("attn", "dense"), dtype),
        }
    # tied-embedding aliasing is realised at the state level (the training
    # state exposes `lm_head` as the same buffer as `embed`); inside the
    # model we read cfg.tie_embeddings.
    return params


def abstract_params(cfg: ArchConfig, dtype=None):
    """ShapeDtypeStruct pytree of the parameters (no allocation beyond a key)."""
    key = jax.random.key(0)
    return jax.eval_shape(lambda k: init_params(cfg, k, dtype), key)


# ---------------------------------------------------------------------------
# layer application (shared by forward & decode)
# ---------------------------------------------------------------------------

def _positions_of(batch: dict, cfg: ArchConfig, seq: int, bsz: int,
                  offset=0):
    if cfg.rope_type == "mrope":
        if "positions_thw" in batch:
            return batch["positions_thw"]
        pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
        pos = jnp.broadcast_to(pos, (bsz, seq))
        return jnp.stack([pos, pos, pos], axis=-1)
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
    return jnp.broadcast_to(pos, (bsz, seq))


def _sinusoidal_embed(positions: Array, d: int) -> Array:
    """In-graph sinusoidal positional embedding. positions [B,S] -> [B,S,d]."""
    half = d // 2
    inv = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                  * (np.log(10_000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * inv
    out = jnp.zeros((*positions.shape, d), jnp.float32)
    out = out.at[..., 0::2].set(jnp.sin(ang))
    out = out.at[..., 1::2].set(jnp.cos(ang))
    return out


def _apply_layer(p: dict, cfg: ArchConfig, spec: LayerSpec, x: Array,
                 positions, enc_out: Optional[Array]) -> Tuple[Array, Array]:
    """Full-sequence layer. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.kind == "attn":
        if cfg.mla is not None:
            y = layers.mla_forward(p["attn"], cfg, h, positions)
        else:
            y = layers.gqa_forward(p["attn"], cfg, h, positions)
    else:
        y = mamba.ssm_forward(p["ssm"], cfg, h)
    x = x + y
    if spec.cross:
        h = layers.rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        x = x + layers.cross_attn_forward(p["cross"], cfg, h, enc_out)
    if spec.ffn == "dense":
        h = layers.rmsnorm(p["norm2"], x, cfg.norm_eps)
        x = x + layers.mlp_forward(p["mlp"], h)
    elif spec.ffn == "moe":
        h = layers.rmsnorm(p["norm2"], x, cfg.norm_eps)
        y = moe_lib.moe_forward(p["moe"], cfg, h)
        aux = moe_lib.aux_load_balance_loss(
            p["moe"]["router"], h.reshape(-1, h.shape[-1]), cfg.moe)
        x = x + y
    return x, aux


def _run_stages(stages_params: dict, stage_specs: List[StageSpec],
                cfg: ArchConfig, x: Array, positions,
                enc_out: Optional[Array], *, remat: bool,
                unroll: bool = False,
                hidden_sharding=None) -> Tuple[Array, Array]:
    """Apply all stages.  ``unroll=True`` replaces the lax.scan over units
    with a python loop (no while op in HLO) — used by the dry-run's cost
    calibration (XLA cost analysis counts a while body once, not x trip
    count) and available as a perf lever (scan-vs-unroll trade-off)."""
    aux_total = jnp.zeros((), jnp.float32)
    if hidden_sharding is not None:
        x = jax.lax.with_sharding_constraint(x, hidden_sharding)
    for i, stage in enumerate(stage_specs):
        sp = stages_params[f"stage_{i}"]

        def unit_body(carry, unit_params, _stage=stage):
            h, aux = carry
            for j, spec in enumerate(_stage.unit):
                h, a = _apply_layer(unit_params[f"sub_{j}"], cfg, spec, h,
                                    positions, enc_out)
                aux = aux + a
            return (h, aux)

        body = unit_body
        if remat:
            body = jax.checkpoint(unit_body)

        if unroll:
            carry = (x, aux_total)
            for u in range(stage.n_units):
                unit_params = jax.tree.map(lambda a, _u=u: a[_u], sp)
                carry = body(carry, unit_params)
            x, aux_total = carry
        else:
            def scan_step(carry, unit_params, _body=body):
                return _body(carry, unit_params), None

            (x, aux_total), _ = jax.lax.scan(scan_step, (x, aux_total), sp)
        if hidden_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, hidden_sharding)
    return x, aux_total


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def take_rows(table: Array, ids: Array) -> Array:
    """``table[ids]``, with the result sharded like ``ids`` and replicated
    along the table's row width.  On an explicitly sharded mesh the table
    and the ids may both be split over the same axis (embed ``P(model,
    data)``, tokens ``P(data, None)``), and the gather's inferred output
    spec would name that axis twice; so the spec is stated."""
    sharding = jax.typeof(ids).sharding
    if not sharding.mesh.explicit_axes:
        return table[ids]
    spec = jax.sharding.PartitionSpec(*sharding.spec, None)
    return table.at[ids].get(
        out_sharding=jax.sharding.NamedSharding(sharding.mesh, spec))


def embed_inputs(cfg: ArchConfig, params: dict, batch: dict) -> Array:
    if "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = take_rows(params["embed"], batch["tokens"])
    return x


def forward(cfg: ArchConfig, params: dict, batch: dict, *,
            training: bool = False, remat: Optional[bool] = None,
            return_aux: bool = False, unroll: bool = False,
            hidden_sharding=None):
    """Full-sequence forward. Returns logits [B,S,V] (and aux dict)."""
    remat = training if remat is None else remat
    x = embed_inputs(cfg, params, batch)
    bsz, seq, d = x.shape
    positions = _positions_of(batch, cfg, seq, bsz)
    if cfg.rope_type == "none":
        pos2d = positions if positions.ndim == 2 else positions[..., 0]
        x = (x.astype(jnp.float32) + _sinusoidal_embed(pos2d, d)).astype(x.dtype)

    enc_out = None
    if cfg.enc_dec:
        enc_out = encode(cfg, params, batch, remat=remat, unroll=unroll)

    x, aux = _run_stages(params["stages"], build_stages(cfg), cfg, x,
                         positions, enc_out, remat=remat, unroll=unroll,
                         hidden_sharding=hidden_sharding)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(cfg, params, x)

    aux_d = {"moe_aux": aux}
    if cfg.mtp and training:
        aux_d["mtp_logits"] = _mtp_logits(cfg, params, x, batch, positions)
    if return_aux:
        return logits, aux_d
    return logits


def encode(cfg: ArchConfig, params: dict, batch: dict, *, remat: bool,
           unroll: bool = False) -> Array:
    enc_x = batch["enc_embeds"].astype(jnp.dtype(cfg.dtype))
    bsz, s_enc, d = enc_x.shape
    pos = jnp.broadcast_to(jnp.arange(s_enc, dtype=jnp.int32)[None, :],
                           (bsz, s_enc))
    enc_x = (enc_x.astype(jnp.float32)
             + _sinusoidal_embed(pos, d)).astype(enc_x.dtype)
    enc = params["encoder"]
    enc_x, _ = _run_stages(enc["stages"], encoder_stages(cfg), cfg, enc_x,
                           pos, None, remat=remat, unroll=unroll)
    return layers.rmsnorm(enc["final_norm"], enc_x, cfg.norm_eps)


def unembed(cfg: ArchConfig, params: dict, x: Array) -> Array:
    if cfg.tie_embeddings or "lm_head" not in params:
        w = params["embed"].T
    else:
        w = params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, w,
                      preferred_element_type=jnp.float32)


def _mtp_logits(cfg, params, h_final, batch, positions):
    """DeepSeek-V3-style multi-token prediction: one extra block predicting
    token t+2 from [h_t ; embed(token_{t+1})]."""
    mtp = params["mtp"]
    tok = batch["tokens"]
    nxt = jnp.concatenate([tok[:, 1:], tok[:, -1:]], axis=1)
    e_next = params["embed"][nxt]
    h = jnp.concatenate([layers.rmsnorm(mtp["norm"], h_final, cfg.norm_eps),
                         e_next], axis=-1)
    h = jnp.einsum("bsk,kd->bsd", h, mtp["proj"],
                   preferred_element_type=jnp.float32).astype(h_final.dtype)
    h, _ = _apply_layer(mtp["block"], cfg, LayerSpec("attn", "dense"), h,
                        positions, None)
    return unembed(cfg, params, h)


# ---------------------------------------------------------------------------
# decode (one token against caches)
# ---------------------------------------------------------------------------

def _init_layer_cache(cfg: ArchConfig, spec: LayerSpec, batch: int, seq: int,
                      dtype) -> dict:
    c: Dict[str, Any] = {}
    if spec.kind == "attn":
        if cfg.mla is not None:
            c["attn"] = layers.mla_cache_init(cfg, batch, seq, dtype)
        else:
            c["attn"] = layers.gqa_cache_init(cfg, batch, seq, dtype)
    else:
        c["ssm"] = mamba.ssm_cache_init(cfg, batch, dtype)
    return c


def init_caches(cfg: ArchConfig, batch: int, seq: int, dtype=None,
                enc_seq: int = 0) -> dict:
    """Cache pytree: per stage, leaves stacked along n_units."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    caches: Dict[str, Any] = {"stages": {}}
    for i, stage in enumerate(build_stages(cfg)):
        def unit_cache(_, _stage=stage):
            return {f"sub_{j}": _init_layer_cache(cfg, spec, batch, seq, dtype)
                    for j, spec in enumerate(_stage.unit)}
        caches["stages"][f"stage_{i}"] = jax.vmap(unit_cache)(
            jnp.arange(stage.n_units))
    if cfg.enc_dec:
        caches["enc_out"] = jnp.zeros((batch, enc_seq or seq, cfg.d_model),
                                      dtype=dtype)
    return caches


def _decode_layer(p: dict, c: dict, cfg: ArchConfig, spec: LayerSpec,
                  x: Array, positions, enc_out) -> Tuple[Array, dict]:
    new_c: Dict[str, Any] = {}
    h = layers.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.kind == "attn":
        if cfg.mla is not None:
            y, new_c["attn"] = layers.mla_decode(p["attn"], cfg, h, c["attn"],
                                                 positions)
        else:
            y, new_c["attn"] = layers.gqa_decode(p["attn"], cfg, h, c["attn"],
                                                 positions)
    else:
        y, new_c["ssm"] = mamba.ssm_decode(p["ssm"], cfg, h, c["ssm"])
    x = x + y
    if spec.cross:
        h = layers.rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        x = x + layers.cross_attn_forward(p["cross"], cfg, h, enc_out)
    if spec.ffn == "dense":
        h = layers.rmsnorm(p["norm2"], x, cfg.norm_eps)
        x = x + layers.mlp_forward(p["mlp"], h)
    elif spec.ffn == "moe":
        h = layers.rmsnorm(p["norm2"], x, cfg.norm_eps)
        y = moe_lib.moe_forward(p["moe"], cfg, h)
        x = x + y
    return x, new_c


def decode_step(cfg: ArchConfig, params: dict, caches: dict, batch: dict,
                *, unroll: bool = False) -> Tuple[Array, dict]:
    """One-token decode. batch: {"tokens": [B,1]} (vlm may pass embeds).
    Returns (logits [B,1,V], new caches)."""
    x = embed_inputs(cfg, params, batch)
    bsz, _, d = x.shape
    index = batch["index"]  # scalar int32: current cache fill
    if cfg.rope_type == "mrope":
        pos = jnp.broadcast_to(index[None, None], (bsz, 1)).astype(jnp.int32)
        positions = jnp.stack([pos, pos, pos], axis=-1)
    else:
        positions = jnp.broadcast_to(index[None, None], (bsz, 1)).astype(jnp.int32)
    if cfg.rope_type == "none":
        x = (x.astype(jnp.float32)
             + _sinusoidal_embed(positions, d)).astype(x.dtype)

    enc_out = caches.get("enc_out")
    new_caches: Dict[str, Any] = {"stages": {}}
    if enc_out is not None:
        new_caches["enc_out"] = enc_out

    for i, stage in enumerate(build_stages(cfg)):
        sp = params["stages"][f"stage_{i}"]
        sc = caches["stages"][f"stage_{i}"]

        def scan_step(carry, xs, _stage=stage):
            h = carry
            unit_p, unit_c = xs
            new_unit_c = {}
            for j, spec in enumerate(_stage.unit):
                h, nc = _decode_layer(unit_p[f"sub_{j}"], unit_c[f"sub_{j}"],
                                      cfg, spec, h, positions, enc_out)
                new_unit_c[f"sub_{j}"] = nc
            return h, new_unit_c

        if unroll:
            outs = []
            for u in range(stage.n_units):
                unit_p = jax.tree.map(lambda a, _u=u: a[_u], sp)
                unit_c = jax.tree.map(lambda a, _u=u: a[_u], sc)
                x, nc = scan_step(x, (unit_p, unit_c))
                outs.append(nc)
            new_sc = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        else:
            x, new_sc = jax.lax.scan(scan_step, x, (sp, sc))
        new_caches["stages"][f"stage_{i}"] = new_sc

    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(cfg, params, x)
    return logits, new_caches
