"""Fused on-device delta pipeline: hash + diff + dirty-chunk compaction in
one Pallas pass over HBM (DESIGN.md §15)."""
from repro.kernels.delta_pack.kernel import delta_pack_pallas  # noqa: F401
from repro.kernels.delta_pack.ops import DeltaPack, delta_pack  # noqa: F401
from repro.kernels.delta_pack.ref import delta_pack_ref  # noqa: F401
