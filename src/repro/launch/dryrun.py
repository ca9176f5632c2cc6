"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the REAL step function (full train_step with
AdamW update for train_4k; prefill forward; one-token decode with a
seq_len KV/state cache), lowers it with ShapeDtypeStruct stand-ins (no
allocation), compiles it for the production mesh, and records
``memory_analysis()`` (proves it fits) + ``cost_analysis()`` (FLOPs and
bytes for the roofline) + the per-device collective byte count parsed
from the post-SPMD HLO.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
"""

import argparse
import json
import os
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.registry import ARCHS, all_cells, shape_applicable
from repro.launch.mesh import fitted_shardings, make_production_mesh
from repro.models.model_api import SHAPES, build_model
from repro.optim.adamw import OptConfig, init_opt_state, make_train_step

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\)|\S+))\s+(" + "|".join(_COLLECTIVES) + r")[\.\s(]"
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device communication bytes by collective kind, from the
    post-partitioning HLO (result-shape bytes per op; see EXPERIMENTS.md
    for the convention)."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m:
            out[m.group(2)] += _shape_bytes(m.group(1))
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _apply_overrides(cfg, overrides: Optional[Dict[str, Any]]):
    if not overrides:
        return cfg
    import dataclasses as _dc

    typed = {}
    for k, v in overrides.items():
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            typed[k] = v in (True, "true", "True", "1", 1)
        elif isinstance(cur, int):
            typed[k] = int(v)
        elif isinstance(cur, float):
            typed[k] = float(v)
        else:
            typed[k] = v
    return _dc.replace(cfg, **typed)


def build_step(arch: str, shape_name: str, overrides: Optional[Dict[str, Any]] = None):
    """Returns (fn, arg_structs, in_specs, out_specs_or_None)."""
    cfg = _apply_overrides(get_config(arch), overrides)
    model = build_model(cfg)
    sh = SHAPES[shape_name]
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(model.init, key)

    if sh.kind == "train":
        pspecs = model.param_specs("train")
        opt_shape = jax.eval_shape(init_opt_state, params_shape)
        from repro.optim.adamw import opt_state_specs, zero1_opt_specs

        ospecs = (
            zero1_opt_specs(pspecs, opt_shape) if cfg.fsdp_all_axes else opt_state_specs(pspecs)
        )
        fn = make_train_step(model.loss, OptConfig())
        batch = model.input_specs(shape_name)
        bspecs = model.batch_specs(shape_name)
        metric_specs = {"lr": P(), "grad_norm": P(), "loss": P()}
        return fn, (params_shape, opt_shape, batch), (pspecs, ospecs, bspecs), (pspecs, ospecs, metric_specs)

    if sh.kind == "prefill":
        pspecs = model.param_specs("serve")
        batch = model.input_specs(shape_name)
        bspecs = model.batch_specs(shape_name)
        return model.prefill, (params_shape, batch), (pspecs, bspecs), P()

    # decode
    pspecs = model.param_specs("serve")
    inputs = model.input_specs(shape_name)
    ispecs = model.batch_specs(shape_name)
    fn = lambda p, t, c, pos: model.decode_step(p, t, c, pos)
    out_specs = (P(), ispecs["cache"])
    return (
        fn,
        (params_shape, inputs["token"], inputs["cache"], inputs["pos"]),
        (pspecs, ispecs["token"], ispecs["cache"], ispecs["pos"]),
        out_specs,
    )


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    verbose: bool = True,
    overrides: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, in_specs, out_specs = build_step(arch, shape_name, overrides)
    in_sh = fitted_shardings(in_specs, args, mesh)
    out_shapes = jax.eval_shape(fn, *args)
    out_sh = fitted_shardings(out_specs, out_shapes, mesh)
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    from repro.launch.roofline import cost_analysis_dict

    mem = compiled.memory_analysis()
    cost = cost_analysis_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    report = {
        "arch": arch,
        "shape": shape_name,
        "overrides": overrides or {},
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.devices.size,
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": float(cost.get("flops", -1.0)),
        "bytes_per_device": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes_per_device": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
    }
    if verbose:
        print(json.dumps(report))
        sys.stdout.flush()
    return report


def main() -> None:
    # 512 host devices stand in for the production mesh; JAX reads the
    # flag when it first initializes a backend, which is after this line
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true", help="use the 2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL reports here")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="config field overrides (perf experiments)")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.set) or None

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch, shape in cells:
        cfg = get_config(arch)
        if not shape_applicable(cfg, shape):
            continue
        for mp in meshes:
            try:
                report = run_cell(arch, shape, mp, overrides=overrides)
            except Exception as e:  # a failure here is a bug in our system
                failures += 1
                report = {
                    "arch": arch, "shape": shape,
                    "mesh": "pod2x16x16" if mp else "16x16",
                    "ok": False, "error": f"{type(e).__name__}: {e}",
                }
                print(json.dumps(report))
                traceback.print_exc()
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(report) + "\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
