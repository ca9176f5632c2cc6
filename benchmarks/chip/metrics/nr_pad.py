"""Padded event-horizon width (slots per lane) of the window's batches,
mean over batches: the ``NR`` the device loop's state is sized by."""


def read(ctx):
    xs = [b["nr_pad"] for b in ctx.batches if "nr_pad" in b]
    return sum(xs) / len(xs) if xs else None
