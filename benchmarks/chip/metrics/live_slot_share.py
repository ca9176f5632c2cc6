"""The share of padded request slots ever live at once: the ``live_peak``
counter (most requests ready or running at once in any lane) over
``nr_pad``, mean over the batches of one recorded pass (``obspass``)."""

import obspass


def collect(ctx):
    obspass.ensure(ctx)


def read(ctx):
    return obspass.metric(ctx, "live_slot_share")
