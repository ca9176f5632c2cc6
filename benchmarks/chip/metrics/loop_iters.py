"""The device loop's true trip count (the most iterations any lane of a
batch ran, ``engine_batch._Out.iters``; counter ``iters_max``), mean over
the batches of one recorded pass over the pool (``obspass``)."""

import obspass


def collect(ctx):
    obspass.ensure(ctx)


def read(ctx):
    return obspass.metric(ctx, "loop_iters")
