"""The lower-precision control of the correctness check.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11 12 13 [--mode native]

Runs one batch of the cell at its own size per seed (its trial seeds
drawn from the seed, not the cell's pool), in place of the
program's software binary64, and reports how many of the checked lanes
differ from the reference, exactly as a benchmark run checks them.  The
check has to fail every seed.

* ``native`` (the default, on the chip): the program's own float64 path
  (``f64.NATIVE``).  On the TPU that float64 is a pair of float32s, the
  step a later change could be tempted to take.
* ``float32``: the reference itself computed in float32, put in the
  program's place (no device needed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import bench  # noqa: E402
import reference  # noqa: E402


def result_of(res: dict):
    """A reference result shaped like the program's ``SimResult``."""
    per_model = {m: types.SimpleNamespace(released=row[0]) for m, row in enumerate(res["models"])}
    fp = ("ref",) + bench.fingerprint_of(res)
    return types.SimpleNamespace(per_model=per_model, fingerprint=lambda: fp)


def lanes_float32(cell: bench.Cell, seeds):
    plans = reference.plans_for(cell.config, cell.traffic, np.float32)
    return [(s, result_of(reference.simulate(cell.config, cell.traffic, s, np.float32, plans)))
            for s in seeds]


def lanes_native(cell: bench.Cell, seeds, program: bench.Program):
    """One batch through the program with its float64 forced native."""
    from repro.core import f64

    saved = f64.for_platform
    f64.for_platform = lambda platform=None: f64.NATIVE
    try:
        return list(zip(seeds, program.run(seeds)))
    finally:
        f64.for_platform = saved


def readings(workload: str, seeds, mode: str = "native", require_tpu: bool = True):
    cell = bench.Cell(bench.load_benchmark(), workload)
    if mode == "native":
        bench.device_info(require_tpu, cell.chips)
        bench.use_cache()
        program = bench.Program(cell)
    out = []
    for seed in seeds:
        batch = [int(s) for s in np.random.SeedSequence([seed % 2**64, 0])
                 .generate_state(cell.lanes, np.uint32)]
        lanes = (lanes_native(cell, batch, program) if mode == "native"
                 else lanes_float32(cell, batch))
        out.append({"seed": seed, **bench.check_lanes(cell, lanes, seed, log=lambda m: None)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Lower-precision control of the check.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--mode", choices=("native", "float32"), default="native")
    args = ap.parse_args(argv)
    try:
        rows = readings(args.workload, args.seeds, args.mode)
    except bench.NoChip as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 2
    for row in rows:
        print(json.dumps({"workload": args.workload, "mode": args.mode, **row}), flush=True)
    return 0 if all(r["lanes_differing"] > 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
