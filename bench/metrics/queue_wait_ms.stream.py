"""Mean wait (ms) of a request in the scheduler's queue, from submit to the
pop of its group: the scheduler's queue_wait_us over queue_waits, both
counted over the window. It holds the coalescing deadline and any time the
scheduler thread spent on earlier groups."""
from bench.readers import ratio


def read(ctx):
    mean_us = ratio(ctx.stat_delta("queue_wait_us"),
                    ctx.stat_delta("queue_waits"))
    return None if mean_us is None else mean_us / 1e3
