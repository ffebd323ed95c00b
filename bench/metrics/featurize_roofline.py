"""featurize's share of its roofline (%): the bytes the featurizer must
read (float32 numerics, int32 codes) and write (F float32 features) per row
at HBM bandwidth, over the summed device time of the kernel's events."""
from bench.readers import roofline


def read(ctx):
    return roofline(ctx, "featurize", 0.0, ctx.work["featurize_bytes_per_row"])
