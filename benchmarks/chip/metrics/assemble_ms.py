"""Host assembly (``assemble_batch``: the one device-to-host copy,
per-lane counts, the retained-accuracy replay), mean milliseconds per
window batch."""


def read(ctx):
    xs = [b["assemble_s"] for b in ctx.batches if "assemble_s" in b]
    return 1e3 * sum(xs) / len(xs) if xs else None
