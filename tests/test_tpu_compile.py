"""Compile rehearsals for a TPU v5e chip that is described, not attached.

Each test compiles one program of the main path at its real size for one
chip of a ``v5e:2x2`` topology: the batch engine's ``_run_trials`` at
three padded widths (and the layout of its latency planes there), the
jitted Terastal round at NJ 256, the three Pallas kernels compiled (not
interpreted) at model widths, and the llama3.2-1b decode step at
published widths.  What the TPU compiler refuses — a block shape off the
tiling, a Mosaic shape cast, a program that does not fit the chip —
fails here.  Nothing runs, so these say nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""

from __future__ import annotations

import os
import re

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


# _run_trials in the software binary64 it runs on a TPU, B=32: the
# BENCH_batch headline cell (saturation_5x / 4k_1ws2os / terastal /
# poisson, 0.1 s, 96 slots), the same cell at a horizon that pads to 192
# slots and keeps every one in its round, and Table II rates over 2 s
# (multicam_light, Poisson), 256 slots whose round reads a 128-slot
# window: (cell, horizon, padded slots, round slots)
_RUN_TRIALS_PROGRAMS = [
    ("saturation_5x", 0.1, 96, 96),
    ("saturation_5x", 0.17, 192, 192),
    ("multicam_light", 2.0, 256, "window"),
]


@pytest.fixture(scope="module")
def run_trials_programs(one_chip):
    """Each of ``_RUN_TRIALS_PROGRAMS`` staged and compiled once for the
    module: ``(args, static, compiled)``.  About half a minute each."""
    from repro.core import f64
    from repro.core.campaign import _plans_for
    from repro.core.engine_batch import _run_trials, stage_batch
    from repro.core.scheduler import make_scheduler
    from repro.core.simulator import make_arrival_process

    proc = make_arrival_process("poisson")
    programs = []
    with pytest.MonkeyPatch.context() as mp:
        # this process's backend is the CPU: stage as the TPU would
        mp.setattr(f64, "for_platform", lambda platform=None: f64.SOFT)
        for cell, horizon, _, _ in _RUN_TRIALS_PROGRAMS:
            plans, tasks = _plans_for(cell, "4k_1ws2os", 0.90, True)
            staged = stage_batch(plans, tasks, horizon, make_scheduler("terastal"),
                                 list(range(32)),
                                 processes=[t.arrival or proc for t in tasks])
            with jax.enable_x64(True):
                args = _specs(staged.args, one_chip)
                compiled = _run_trials.lower(*args, **staged.static).compile()
            programs.append((args, staged.static, compiled))
    return programs


def test_run_trials_compiles(run_trials_programs):
    """The device-resident trial engine compiles for one chip at each of
    ``_RUN_TRIALS_PROGRAMS``, and its temporaries fit well inside it."""
    from repro.core.engine_batch import ROUND_WINDOW

    for (_, _, nr, win), (args, static, compiled) in zip(_RUN_TRIALS_PROGRAMS,
                                                         run_trials_programs):
        assert args[1].shape[0] == 32 and args[1].dtype == jnp.int64
        assert args[2].shape[-1] == nr
        assert static["win"] == (ROUND_WINDOW if win == "window" else win)
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# an instruction whose result is a [B, a, b] array, with its layout's
# minor-to-major order: the first entry is the dimension in the lanes
_HLO_3D = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[32,(\d+),(\d+)\]\{(\d+),")


def _planes_with_na_in_lanes(hlo: str, na: int) -> tuple[int, int]:
    """(ops shaped like a latency plane, those with the NA axis minor).
    A plane is ``[B, NA, slots]`` or ``[B, slots, NA]``, slots >= 32."""
    planes = na_minor = 0
    for line in hlo.splitlines():
        m = _HLO_3D.match(line)
        if m is None:
            continue
        a, b, minor = (int(g) for g in m.groups())
        if na not in (a, b) or max(a, b) < 32:
            continue
        planes += 1
        na_minor += minor == (1 if a == na else 2)
    return planes, na_minor


def test_run_trials_latency_planes_keep_slots_in_lanes(run_trials_programs):
    """No op shaped like a per-request latency plane puts the NA
    accelerators in the 128 lanes, at 96 slots, 192 slots and 256 slots
    read through the 128-slot window: with NA = 3 minor, one u32 op at
    192 slots touches 768 vregs where slots in the lanes touch 24."""
    for (cell, horizon, nr, _), (args, _, compiled) in zip(_RUN_TRIALS_PROGRAMS,
                                                           run_trials_programs):
        planes, na_minor = _planes_with_na_in_lanes(compiled.as_text(),
                                                    args[0].lat.shape[-1])
        assert planes > 0 and na_minor == 0, (cell, horizon, nr, planes, na_minor)


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
