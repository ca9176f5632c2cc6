"""The device loop (``_run_trials``), from the call to
``block_until_ready``, mean milliseconds per window batch."""


def read(ctx):
    xs = [b["loop_s"] for b in ctx.batches if "loop_s" in b]
    return 1e3 * sum(xs) / len(xs) if xs else None
