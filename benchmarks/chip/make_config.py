"""Write a configuration file: a model mix on a Table I platform, with the
latency tables it is run with.

    python benchmarks/chip/make_config.py --source "<paper URL, tables>" \
        multicam.4k_1ws2os 4k_1ws2os \
        mobilenetv2_ssd:512 resnet50:448 vgg11:384 inceptionv3:299 swin_tiny:224

The tables are the MAESTRO-style profile of each layer on each
accelerator (``repro.costmodel``), and of every feasible layer variant
(gamma in {2, 3}, d2s and s2d) with its accuracy loss, and, for a
model whose layers form a graph and not a chain, ``preds``: each node's
predecessors (node ``i`` is layer ``i``).  They are the
deployment's data, as MAESTRO's tables are the paper's: the benchmark's
reference derives budgets, the variant choice and every scheduling
decision from them, and the harness checks that the program's offline
plans carry exactly these numbers.  The file is written once, when a
configuration is added; later changes to the cost model then show as a
mismatch instead of moving the yardstick.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

GAMMAS = (2, 3)
DIRECTIONS = ("d2s", "s2d")


def tables(platform_name: str, models):
    from repro.core.accuracy import layer_variant_loss
    from repro.costmodel import dnn_zoo
    from repro.costmodel.layers import make_variant, variant_feasible
    from repro.costmodel.maestro import PLATFORMS, layer_latency, model_latency_table

    platform = PLATFORMS[platform_name]
    out = []
    for name, res in models:
        model = getattr(dnn_zoo, name)(res)
        lat = model_latency_table(model.layers, platform)
        variants, loss = {}, {}
        for l, spec in enumerate(model.layers):
            cands = {}
            for g in GAMMAS:
                rows = {d: [float(layer_latency(make_variant(spec, g, d), a, platform))
                            for a in platform.accelerators]
                        for d in DIRECTIONS if variant_feasible(spec, g, d)}
                if rows:
                    cands[str(g)] = rows
            if cands:
                variants[str(l)] = cands
                loss[str(l)] = {g: layer_variant_loss(model.name, spec.name, model.redundancy, int(g))
                                for g in cands}
        entry = {"model": model.name, "resolution": res, "n_layers": len(model.layers),
                 "lat": lat.tolist(), "variants": variants, "loss": loss}
        if model.dag is not None and not model.dag.is_linear:
            entry["preds"] = [list(ps) for ps in model.dag.preds]
        out.append(entry)
    accs = [{"name": a.name, "dataflow": a.dataflow.value, "pes": a.pes}
            for a in platform.accelerators]
    return accs, out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("name")
    ap.add_argument("platform")
    ap.add_argument("models", nargs="+", help="model:resolution, in entry order")
    ap.add_argument("--source", required=True,
                    help="the paper and its tables that define this deployment")
    ap.add_argument("--scheduler", default="terastal")
    ap.add_argument("--theta", type=float, default=0.90)
    args = ap.parse_args()
    models = [(m.split(":")[0], int(m.split(":")[1])) for m in args.models]
    accs, mods = tables(args.platform, models)
    cfg = {"name": args.name, "source": args.source, "platform": args.platform,
           "accelerators": accs, "scheduler": args.scheduler, "theta": args.theta,
           "enable_variants": True, "models": mods,
           "assumed": {
               "resolution": "input resolutions of the repo's load calibration "
                             "(the paper publishes no MAESTRO latencies)",
               "lat": "per-layer latency in seconds on each accelerator, from the "
                      "repo's MAESTRO-style cost model",
               "loss": "relative accuracy loss of each variant, from the repo's "
                       "accuracy model"}}
    path = os.path.join(HERE, "configs", args.name + ".json")
    with open(path, "w") as f:
        json.dump(cfg, f, separators=(",", ":"))
        f.write("\n")
    print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
