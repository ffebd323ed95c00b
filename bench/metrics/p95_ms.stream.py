"""95th-percentile request latency (ms) of the traced window, from due time
to result, host clock; a failed request counts as missing. Host stalls of
0.1-2 s in some runs spread it past any bound an end-to-end metric could
hold, so it is read here, beside the bounded median."""
from bench.readers import latency_percentile


def read(ctx):
    return latency_percentile(ctx, 95)
