"""Pallas TPU kernels for the paper's compute hot-spot (computeSupports).

``support_fine``  — fine-grained edge-tile intersection kernel (Alg. 3).
``support_dense`` — blocked (U@U)∘U MXU kernel (Alg. 1).
Validated in interpret mode against ``ref.py`` on CPU; written for TPU
(BlockSpec VMEM tiling, MXU dots, VPU compare-reduce schedules).
"""

from . import ops, ref
from .support_dense import support_dense_pallas
from .support_fine import support_fine_pallas

__all__ = [
    "ops",
    "ref",
    "support_dense_pallas",
    "support_fine_pallas",
]
