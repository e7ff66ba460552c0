"""Divisibility-aware sharding rules for all architectures and meshes.

Scheme (MaxText-style 2-D + optional pod axis):
  - FSDP: parameter d_model-like dims sharded over ("pod","data") / ("data",)
  - TP:   heads / ff / vocab dims sharded over "model"
  - EP:   expert dim sharded over "data" (experts per group), ff over "model"
  - activations: batch over ("pod","data"); decode caches shard the *sequence*
    dim over "model" (uniform across archs — works for kv_heads < mesh model
    size, e.g. whisper's 20 heads or smollm's 15)

Every choice is guarded by a divisibility check with a deterministic
fallback (head-TP -> head_dim-TP -> replicate), so smollm (15 heads) and
whisper (20 heads, vocab 51866) lower cleanly on a 16-way model axis.
Specs are derived from parameter *path names*, so they apply equally to
optimizer moments (same tree structure).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig


def _divides(n: int, by: int) -> bool:
    return by > 0 and n % by == 0


class ShardingRules:
    """Sharding policy. Tunables:

    - ``fsdp_pods``: fold the pod axis into the FSDP group.
    - ``expert_pod_shard``: shard the MoE expert dim over ("pod","data")
      instead of "data" alone (halves expert params/moments per device on
      the multi-pod mesh when n_experts divides pod*data).
    - ``attn_fallback``: when n_heads doesn't divide the model axis —
      "head_dim" shards head_dim over model (TP with per-layer reductions);
      "replicate" keeps attention weights replicated and data-parallel only
      (kills the per-layer attention collectives; costs memory).
    - ``seq_shard_activations``: constrain the residual stream to
      P(batch, "model", None) between stages (Megatron-SP style RS/AG
      instead of all-reduce).
    """

    def __init__(self, cfg: ArchConfig, mesh: Mesh, *,
                 fsdp_pods: bool = True,
                 expert_pod_shard: bool = False,
                 attn_fallback: str = "head_dim",
                 seq_shard_activations: bool = False,
                 expert_fsdp_pod: bool = False,
                 moe_dispatch_shard: bool = False,
                 dp_only: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.expert_pod_shard = expert_pod_shard
        self.expert_fsdp_pod = expert_fsdp_pod
        self.moe_dispatch_shard = moe_dispatch_shard
        self.attn_fallback = attn_fallback
        self.seq_shard_activations = seq_shard_activations
        self.dp_only = dp_only
        names = mesh.axis_names
        self.model_axis = "model" if "model" in names else None
        self.data_axis = "data" if "data" in names else None
        self.pod_axis = "pod" if "pod" in names else None
        self.model_size = mesh.shape.get("model", 1)
        self.data_size = mesh.shape.get("data", 1)
        self.pod_size = mesh.shape.get("pod", 1)
        # FSDP group: pod axis folds into FSDP for huge models
        if dp_only:
            # ZeRO-3 regime: every axis is data-parallel; params/moments
            # fully sharded over the flat device space; no tensor parallel.
            axes = [a for a in (self.pod_axis, self.data_axis,
                                self.model_axis) if a]
            self.fsdp = tuple(axes)
            self.fsdp_size = self.pod_size * self.data_size * self.model_size
            self.batch_axes = tuple(axes)
            self.batch_size_div = self.fsdp_size
            self.model_axis = None
            self.model_size = 1
            return
        if self.pod_axis and fsdp_pods:
            self.fsdp: Any = (self.pod_axis, self.data_axis)
            self.fsdp_size = self.pod_size * self.data_size
        else:
            self.fsdp = self.data_axis
            self.fsdp_size = self.data_size
        self.batch_axes: Any = ((self.pod_axis, self.data_axis)
                                if self.pod_axis else self.data_axis)
        self.batch_size_div = self.pod_size * self.data_size

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _fsdp_if(self, dim: int):
        return self.fsdp if _divides(dim, self.fsdp_size) else None

    def _model_if(self, dim: int):
        return self.model_axis if _divides(dim, self.model_size) else None

    def _batch_if(self, dim: int):
        if _divides(dim, self.batch_size_div):
            return self.batch_axes
        if _divides(dim, self.data_size):
            return self.data_axis
        return None

    # ------------------------------------------------------------------
    # parameters (and optimizer moments — same paths)
    # ------------------------------------------------------------------
    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        cfg = self.cfg
        leaf = path.split("/")[-1]
        parent = path.split("/")[-2] if "/" in path else ""

        if leaf in ("scale", "conv_b", "dt_bias", "A_log", "D"):
            return P()
        if leaf == "conv_w":
            lead = (None,) * (len(shape) - 2)
            return P(*lead, None, self._model_if(shape[-1]))
        if leaf == "embed":
            return P(self._model_if(shape[0]), self._fsdp_if(shape[1]))
        if leaf == "lm_head":
            return P(self._fsdp_if(shape[0]), self._model_if(shape[1]))
        if leaf == "router":
            lead = (None,) * (len(shape) - 2)
            return P(*lead, self._fsdp_if(shape[-2]), None)

        # MoE expert-stacked weights [*, E, d, f] / [*, E, f, d]
        if leaf in ("w_gate", "w_up", "w_down") and parent == "moe" or \
                (leaf in ("w_gate", "w_up", "w_down") and len(shape) >= 3
                 and "moe" in path):
            lead = (None,) * (len(shape) - 3)      # stacked n_units dims
            e, a, b = shape[-3], shape[-2], shape[-1]
            if self.expert_pod_shard and \
                    _divides(e, self.pod_size * self.data_size) and \
                    self.pod_axis:
                espec: Any = (self.pod_axis, self.data_axis)
            elif _divides(e, self.data_size):
                espec = self.data_axis
            else:
                espec = None
            # optional ZeRO-style pod-sharding of the expert d_model dim:
            # keeps the 16-way dispatch pattern, halves expert memory on the
            # multi-pod mesh at the cost of a small per-layer weight gather
            dpod = (self.pod_axis if self.expert_fsdp_pod and self.pod_axis
                    else None)
            if leaf == "w_down":                   # [E, f, d]
                d_ok = dpod if dpod and _divides(b, self.pod_size) else None
                return P(*lead, espec, self._model_if(a), d_ok)
            d_ok = dpod if dpod and _divides(a, self.pod_size) else None
            return P(*lead, espec, d_ok, self._model_if(b))

        # dense MLP [*, d, f] / [*, f, d]
        if leaf in ("w_gate", "w_up"):
            lead = (None,) * (len(shape) - 2)
            return P(*lead, self._fsdp_if(shape[-2]), self._model_if(shape[-1]))
        if leaf == "w_down":
            lead = (None,) * (len(shape) - 2)
            return P(*lead, self._model_if(shape[-2]), self._fsdp_if(shape[-1]))

        # attention projections [*, d, H, hd] / wo [*, H, hd, d]
        if leaf in ("wq", "wk", "wv"):
            lead = (None,) * (len(shape) - 3)
            d, h, hd = shape[-3], shape[-2], shape[-1]
            if _divides(h, self.model_size):
                return P(*lead, self._fsdp_if(d), self.model_axis, None)
            if self.attn_fallback == "head_dim" and \
                    _divides(hd, self.model_size):
                return P(*lead, self._fsdp_if(d), None, self.model_axis)
            return P(*lead, self._fsdp_if(d), None, None)
        if leaf == "wo":
            lead = (None,) * (len(shape) - 3)
            h, hd, d = shape[-3], shape[-2], shape[-1]
            if _divides(h, self.model_size):
                return P(*lead, self.model_axis, None, self._fsdp_if(d))
            if self.attn_fallback == "head_dim" and \
                    _divides(hd, self.model_size):
                return P(*lead, None, self.model_axis, self._fsdp_if(d))
            return P(*lead, None, None, self._fsdp_if(d))

        # MLA
        if leaf in ("wq_a", "wkv_a"):
            lead = (None,) * (len(shape) - 2)
            return P(*lead, self._fsdp_if(shape[-2]), None)
        if leaf in ("wq_b", "wkv_b"):
            lead = (None,) * (len(shape) - 3)
            return P(*lead, None, self._model_if(shape[-2]), None)

        # SSM projections [*, d, K] / out_proj [*, d_in, d]
        if leaf == "in_proj":
            lead = (None,) * (len(shape) - 2)
            return P(*lead, self._fsdp_if(shape[-2]), None)
        if leaf == "out_proj":
            lead = (None,) * (len(shape) - 2)
            return P(*lead, self._model_if(shape[-2]), self._fsdp_if(shape[-1]))
        if leaf == "proj":                          # mtp [2d, d]
            lead = (None,) * (len(shape) - 2)
            return P(*lead, self._fsdp_if(shape[-2]), self._model_if(shape[-1]))

        # default: replicate
        return P()

    def param_shardings(self, abstract_params) -> Any:
        from repro.core.namespace import flatten_tree
        flat = flatten_tree(abstract_params)
        specs = {k: NamedSharding(self.mesh, self.param_spec(k, tuple(v.shape)))
                 for k, v in flat.items()}
        from repro.core.namespace import unflatten_tree
        return unflatten_tree(specs)

    # ------------------------------------------------------------------
    # activations / batches / caches
    # ------------------------------------------------------------------
    def batch_spec(self, batch_tree) -> Any:
        def spec(x):
            if not hasattr(x, "shape") or x.ndim == 0:
                return NamedSharding(self.mesh, P())
            b = self._batch_if(x.shape[0])
            return NamedSharding(self.mesh, P(b, *([None] * (x.ndim - 1))))
        return jax.tree.map(spec, batch_tree)

    def cache_spec(self, caches_tree, batch: int) -> Any:
        """Decode caches: batch over data axes, *sequence* dim over model.

        Cache leaves are stacked [n_units, ...]; leaf kinds are identified by
        rank/shape (k/v: [U,B,S,H,hd]; c_kv: [U,B,S,r]; k_rope: [U,B,S,1,hd];
        ssm state: [U,B,H,P,N]; conv: [U,B,W,C]; index: [U])."""
        bspec = self._batch_if(batch)

        def spec(x):
            if not hasattr(x, "shape") or x.ndim <= 1:
                return NamedSharding(self.mesh, P())
            s = list(x.shape)
            if x.ndim == 5 and s[1] == batch:       # k/v cache [U,B,S,H,hd]
                seq_ax = self._model_if(s[2])
                if s[3] == 1:                        # k_rope single head
                    return NamedSharding(self.mesh, P(None, bspec, seq_ax, None, None))
                return NamedSharding(self.mesh, P(None, bspec, seq_ax, None, None))
            if x.ndim == 4 and s[1] == batch:
                # c_kv [U,B,S,r] or ssm state [U,B,H,P] won't occur (state is 5D
                # with U); treat dim2 as seq/heads: shard over model if divisible
                return NamedSharding(self.mesh, P(None, bspec, self._model_if(s[2]), None))
            if x.ndim == 3 and s[1] == batch:        # conv [U,B? ...]
                return NamedSharding(self.mesh, P(None, bspec, None))
            if x.ndim >= 2 and s[0] == batch:        # enc_out [B,S,d]
                return NamedSharding(self.mesh, P(bspec, *([None] * (x.ndim - 1))))
            return NamedSharding(self.mesh, P())
        return jax.tree.map(spec, caches_tree)

    def logits_spec(self, batch: int) -> NamedSharding:
        return NamedSharding(
            self.mesh, P(self._batch_if(batch), None,
                         self._model_if(self.cfg.padded_vocab)))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # activation constraint used at stage boundaries inside the model
    def hidden_spec(self, batch: int, seq: int = 0) -> NamedSharding:
        if self.seq_shard_activations and seq and \
                _divides(seq, self.model_size):
            return NamedSharding(self.mesh,
                                 P(self._batch_if(batch), self.model_axis,
                                   None))
        return NamedSharding(self.mesh,
                             P(self._batch_if(batch), None, None))
