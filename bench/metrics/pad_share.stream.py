"""Share of the rows the device ran that were bucket padding:
rows_padded / (rows_in + rows_padded), the server's counters over the
window."""
from bench.readers import ratio


def read(ctx):
    pad = ctx.stat_delta("rows_padded")
    return ratio(pad, ctx.stat_delta("rows_in") + pad)
