"""Host staging (``stage_batch``: release events, packing, fault epochs),
mean milliseconds per window batch, from the harness's host spans."""


def read(ctx):
    xs = [b["stage_s"] for b in ctx.batches if "stage_s" in b]
    return 1e3 * sum(xs) / len(xs) if xs else None
