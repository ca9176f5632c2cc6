"""Device-loop time over the staged iteration bound (``max_it``), summed
over the window's batches, in microseconds per bound iteration.  The
bound counts every event a lane may pop; the loop's true iteration count
is not reported by the program."""


def read(ctx):
    rows = [b for b in ctx.batches if "loop_s" in b and b.get("max_it")]
    if not rows:
        return None
    return 1e6 * sum(b["loop_s"] for b in rows) / sum(b["max_it"] for b in rows)
