"""The whole step's share of the chip's peak (%): the model's unpadded
GEMM-strategy operations per row times the rows the traced window served
per second, over chips times peak FLOP/s."""
from bench.readers import rows_served


def read(ctx):
    if ctx.events is None or not ctx.peak:
        return None
    rate = rows_served(ctx) / ctx.seconds
    return 100.0 * ctx.work["tree_gemm_flops_per_row"] * rate / (
        ctx.chips * float(ctx.peak["flops_per_s"]))
