"""Pallas TPU kernels for the paper's hot spots (tree GEMM, fused
featurization, relational gather-join and segmented aggregate).

``VMEM_LIMIT_BYTES`` is the scoped-VMEM limit every kernel is compiled with
(each TPU generation from v4 on has at least this much VMEM per core);
block-size choices plan for ``VMEM_BUDGET_BYTES`` of it and leave the rest
to Mosaic's own scratch.
"""

VMEM_LIMIT_BYTES = 32 * 1024 * 1024
VMEM_BUDGET_BYTES = 24 * 1024 * 1024
