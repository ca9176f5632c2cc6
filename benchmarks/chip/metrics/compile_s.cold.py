"""Seconds to trace, lower and compile the device program of the cell's
most frequent staged shape with no cache of any kind: the in-memory
caches cleared and the persistent cache off.  Measured once, after the
window of a ``--trace 1`` run."""

import time


def collect(ctx):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not ctx.bucket_seeds:
        return
    program = ctx.program
    staged = program.stage(ctx.bucket_seeds[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(True):
            t0 = time.perf_counter()
            program.run_trials.lower(*staged.args, **staged.static).compile()
            ctx.probes["compile_s.cold"] = time.perf_counter() - t0
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def read(ctx):
    return ctx.probes.get("compile_s.cold")
