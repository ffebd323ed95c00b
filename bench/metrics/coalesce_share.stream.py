"""Share of served requests that shared a dispatched group with others:
coalesced_requests / requests_served, the server's counters over the
window."""
from bench.readers import ratio


def read(ctx):
    return ratio(ctx.stat_delta("coalesced_requests"),
                 ctx.stat_delta("requests_served"))
