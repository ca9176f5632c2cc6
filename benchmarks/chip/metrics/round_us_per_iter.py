"""Device time per loop iteration in the round (scope ``round``: the
scheduler kernel), microseconds, from the device trace alone: per staged
shape the scope's operation time over the iterations begun in the
profiled stretch, weighted by the iterations each shape's batches ran
in one recorded pass (``obspass``)."""

import obspass


def collect(ctx):
    obspass.ensure(ctx)


def read(ctx):
    return obspass.metric(ctx, "round_us_per_iter")
