"""Device-loop time per true iteration: the ``engine.loop`` spans over the
``iters_max`` counters of one recorded pass over the pool, microseconds
(``obspass``)."""

import obspass


def collect(ctx):
    obspass.ensure(ctx)


def read(ctx):
    return obspass.metric(ctx, "loop_us_per_iter")
