"""Device time per loop iteration in ``bind`` (the row bind, whose
``[NR, NA]`` cache planes are a one-hot select up to 128 slots and a
scatter above), microseconds, from the device trace alone: per staged
shape the scope's operation time over the iterations begun in the
profiled stretch, weighted by the iterations each shape's batches ran
in one recorded pass (``obspass``)."""

import obspass


def collect(ctx):
    obspass.ensure(ctx)


def read(ctx):
    return obspass.metric(ctx, "bind_us_per_iter")
