"""Trace-time sharding hints for layers that GSPMD mis-resolves.

The MoE expert matmul with pod-sharded weights has two legal SPMD
resolutions: all-reduce the [E, capacity, d_ff] output (~86 GB/layer for
DeepSeek-V3 — catastrophic, and what GSPMD picks) or all-gather the weights
(~44 MB/device/layer — ZeRO-style, what we want).  ``moe_weight_gather``
installs per-weight resharding constraints that moe_forward applies at use
time, forcing the gather resolution while the *persistent* weights stay
pod-sharded (the memory win).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

_MOE_WEIGHT_SHARDINGS: Optional[Tuple] = None


def get_moe_weight_shardings():
    return _MOE_WEIGHT_SHARDINGS


@contextlib.contextmanager
def moe_weight_gather(rules):
    """Within this context, traced moe_forward calls re-shard expert weights
    to the dispatch layout (expert dim over data, ff over model, d_model
    replicated) before the expert einsums; with ``moe_dispatch_shard`` the
    scatter/gather dispatch buffers are additionally constrained to
    expert-sharded layouts (all-to-all token shuffle instead of replicated
    buffers)."""
    global _MOE_WEIGHT_SHARDINGS
    gather = getattr(rules, "expert_fsdp_pod", False)
    dispatch = getattr(rules, "moe_dispatch_shard", False)
    if not gather and not dispatch:
        yield
        return
    from jax.sharding import NamedSharding, PartitionSpec as P
    e = rules.data_axis
    m = rules.model_axis
    # moe_forward sees the per-unit slice [E, d, f] (the stacked n_units dim
    # is consumed by the scan/unroll over units)
    gate_up = NamedSharding(rules.mesh, P(e, None, m)) if gather else None
    down = NamedSharding(rules.mesh, P(e, m, None)) if gather else None
    buf_sh = NamedSharding(rules.mesh, P(e, None, None)) if dispatch else None
    h_sh = NamedSharding(rules.mesh, P(e, None, m)) if dispatch else None
    prev = _MOE_WEIGHT_SHARDINGS
    _MOE_WEIGHT_SHARDINGS = (gate_up, gate_up, down, buf_sh, h_sh)
    try:
        yield
    finally:
        _MOE_WEIGHT_SHARDINGS = prev
