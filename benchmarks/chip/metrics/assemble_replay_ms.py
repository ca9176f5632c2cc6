"""Host replay of the retained-accuracy sums in ``assemble_batch`` (span
``engine.assemble.replay``), mean milliseconds per batch of one
recorded pass (``obspass``)."""

import obspass


def collect(ctx):
    obspass.ensure(ctx)


def read(ctx):
    return obspass.metric(ctx, "assemble_replay_ms")
