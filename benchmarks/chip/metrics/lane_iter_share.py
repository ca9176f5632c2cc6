"""The share of the vmapped loop's lane-iterations that pop a real event:
the ``iters_sum`` counters over ``lanes`` x ``iters_max``, pooled over one
recorded pass (``obspass``).  The rest are lanes already drained,
waiting for the slowest."""

import obspass


def collect(ctx):
    obspass.ensure(ctx)


def read(ctx):
    return obspass.metric(ctx, "lane_iter_share")
