"""tree_gemm's share of its roofline (%): the least time of the model's
unpadded GEMM-strategy work, T(2FI + 2IL) per row at the chip's peak FLOP/s
(or its bytes at HBM bandwidth, whichever binds), over the summed device
time of the kernel's trace events."""
from bench.readers import roofline


def read(ctx):
    return roofline(ctx, "tree_gemm", ctx.work["tree_gemm_flops_per_row"],
                    ctx.work["tree_gemm_bytes_per_row"])
