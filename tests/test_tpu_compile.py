"""Compile rehearsals for a TPU v5e chip that is described, not attached.

Each test compiles one program of the main path at its real size for one
chip of a ``v5e:2x2`` topology: the batch engine's ``_run_trials`` at the
headline campaign cell, the jitted Terastal round at NJ 256, the three
Pallas kernels compiled (not interpreted) at model widths, and the
llama3.2-1b decode step at published widths.  What the TPU compiler
refuses — a block shape off the tiling, a Mosaic shape cast, a program
that does not fit the chip — fails here.  Nothing runs, so these say
nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _specs(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a), sharding=sharding),
        tree,
    )


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_run_trials_compiles(one_chip, monkeypatch):
    """The device-resident trial engine, in the software binary64 it runs
    on a TPU, at the BENCH_batch headline cell (saturation_5x / 4k_1ws2os
    / terastal / poisson, B=32, 0.1 s), which reads every slot in its
    round, and at Table II rates over 2 s (multicam_light, Poisson), 256
    slots whose round reads a 128-slot window.  About a minute each: the
    software binary64 makes a large program."""
    from repro.core import f64
    from repro.core.campaign import _plans_for
    from repro.core.engine_batch import ROUND_WINDOW, _run_trials, stage_batch
    from repro.core.scheduler import make_scheduler
    from repro.core.simulator import make_arrival_process

    proc = make_arrival_process("poisson")
    # this process's backend is the CPU: stage as the TPU would
    monkeypatch.setattr(f64, "for_platform", lambda platform=None: f64.SOFT)
    for cell, horizon, nr, win in [("saturation_5x", 0.1, None, None),
                                   ("multicam_light", 2.0, 256, ROUND_WINDOW)]:
        plans, tasks = _plans_for(cell, "4k_1ws2os", 0.90, True)
        staged = stage_batch(plans, tasks, horizon, make_scheduler("terastal"),
                             list(range(32)), processes=[t.arrival or proc for t in tasks])
        with jax.enable_x64(True):
            args = _specs(staged.args, one_chip)
            assert args[1].shape[0] == 32 and args[1].dtype == jnp.int64
            if nr is not None:
                assert args[2].shape[-1] == nr and staged.static["win"] == win
            compiled = _run_trials.lower(*args, **staged.static).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_terastal_round_compiles(one_chip):
    """The jitted Terastal round at NJ 256, in the software binary64 it
    runs on a TPU (float inputs as int64 bit patterns)."""
    from repro.core.scheduler_jax import RoundInputs, round_jit

    NJ, NA = 256, 4
    f64 = jnp.int64
    with jax.enable_x64(True):
        inp = RoundInputs(
            ready_mask=jax.ShapeDtypeStruct((NJ,), jnp.bool_, sharding=one_chip),
            vdl=jax.ShapeDtypeStruct((NJ,), f64, sharding=one_chip),
            vdl_next=jax.ShapeDtypeStruct((NJ,), f64, sharding=one_chip),
            next_min=jax.ShapeDtypeStruct((NJ,), f64, sharding=one_chip),
            lat=jax.ShapeDtypeStruct((NJ, NA), f64, sharding=one_chip),
            lat_var=jax.ShapeDtypeStruct((NJ, NA), f64, sharding=one_chip),
            tau=jax.ShapeDtypeStruct((NA,), f64, sharding=one_chip),
            idle_mask=jax.ShapeDtypeStruct((NA,), jnp.bool_, sharding=one_chip),
        )
        round_jit.lower(inp, mode="ef", soft=True).compile()


def _decode_attn():
    """llama3.2-1b decode: B=8, 32 query heads over 8 KV heads, Dh=64, L=4096."""
    from functools import partial

    from repro.kernels.decode_attn.kernel import decode_attn_pallas

    B, L, H, Hkv, Dh = 8, 4096, 32, 8, 64
    fn = partial(decode_attn_pallas, chunk=512, interpret=False)
    shapes = [((B, H, Dh), jnp.bfloat16), ((B, L, Hkv, Dh), jnp.bfloat16),
              ((B, L, Hkv, Dh), jnp.bfloat16), ((B,), jnp.int32)]
    return fn, shapes


def _ssd_scan():
    """mamba2-1.3b mixer: L=2048, H=64 heads of P=64, state N=128, chunk 256."""
    from functools import partial

    from repro.kernels.ssd_scan.kernel import ssd_scan_pallas

    Bt, L, H, P, N = 1, 2048, 64, 64, 128
    fn = partial(ssd_scan_pallas, chunk=256, interpret=False)
    shapes = [((Bt, L, H, P), jnp.bfloat16), ((Bt, L, H), jnp.float32),
              ((Bt, L, N), jnp.bfloat16), ((Bt, L, N), jnp.bfloat16),
              ((Bt, L, H), jnp.float32)]
    return fn, shapes


def _s2d_conv(hw, c):
    """A VGG conv layer's gamma=2 variant at its published width."""
    from functools import partial

    from repro.kernels.s2d_conv.kernel import s2d_conv_pallas

    fn = partial(s2d_conv_pallas, gamma=2, interpret=False)
    shapes = [((1, hw, hw, c), jnp.bfloat16), ((c // 4, c // 4), jnp.bfloat16)]
    return fn, shapes


@pytest.mark.parametrize("make", [
    _decode_attn,
    _ssd_scan,
    lambda: _s2d_conv(56, 256),
    lambda: _s2d_conv(28, 512),
], ids=["decode_attn", "ssd_scan", "s2d_conv_56x56x256", "s2d_conv_28x28x512"])
def test_kernel_compiles(one_chip, make):
    fn, shapes = make()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert _has_kernel(compiled)


def test_llama_decode_step_compiles(one_chip):
    """llama3.2-1b at published widths, bf16, batch 2 against a 64-slot cache."""
    from repro.configs import get_config
    from repro.models.model_api import build_model

    cfg = get_config("llama3.2-1b")
    assert cfg.d_model == 2048 and cfg.n_layers == 16 and cfg.dtype == "bfloat16"
    model = build_model(cfg)
    params = _specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    cache = _specs(jax.eval_shape(lambda: model.init_cache(2, 64)), one_chip)
    tok = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step).lower(params, tok, cache, pos).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 2 << 30  # the full 1.2B parameters
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 << 30
