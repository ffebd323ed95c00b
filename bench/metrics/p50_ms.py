"""Median request latency (ms), from due time to result, host clock."""
from bench.readers import latency_percentile


def read(ctx):
    return latency_percentile(ctx, 50)
