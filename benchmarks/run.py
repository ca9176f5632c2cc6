"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (micro section) plus a
per-figure results table and a claim-validation summary.  Set
REPRO_BENCH_FAST=1 for a quick pass, or run with ``--smoke`` (CI): a
tiny grid / 1 seed / short horizon per benchmark, claim results printed
but informational — the smoke pass exists so every registered benchmark
script is executed end-to-end and cannot silently rot.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; make the `benchmarks` package importable regardless of cwd.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _section(title: str) -> None:
    print(f"\n## {title}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny grid, 1 seed, short horizon; claim failures do not fail "
        "the run (statistics are meaningless at smoke scale) — only "
        "crashes do",
    )
    ap.add_argument(
        "--adaptive",
        action="store_true",
        help="run the campaign figures (fig5/fig7/fig8) through the "
        "sequential adaptive sampler (stop cells when the CIs separate) "
        "instead of the fixed seed grid",
    )
    args = ap.parse_args(argv)
    if args.smoke:
        # set before the benchmark modules read them at run() time
        os.environ["REPRO_BENCH_FAST"] = "1"
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    if args.adaptive:
        os.environ["REPRO_BENCH_ADAPTIVE"] = "1"

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        ablation_backfill,
        bench_batch_trials,
        bench_campaign_throughput,
        bench_lm_serving,
        bench_micro,
        bench_sampler_efficiency,
        bench_scheduler_round,
        fig3_vgg11_latency,
        fig4_accuracy_vs_variants,
        fig5_miss_rate,
        fig6_threshold_sweep,
        fig7_arrival_robustness,
        fig8_adaptive_budgets,
        fig9_overload_control,
        fig10_fault_tolerance,
        fig11_dag_workloads,
        fig12_fault_budgets,
        table_storage,
    )

    all_claims = []
    t0 = time.time()

    _section("micro (name,us_per_call,derived)")
    micro = bench_micro.run()
    for r in micro:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    all_claims += [("bench_micro", *c) for c in bench_micro.claims(micro)]

    for mod, title in [
        (fig3_vgg11_latency, "fig3: VGG11 per-layer WS/OS latency + variants"),
        (fig4_accuracy_vs_variants, "fig4: accuracy vs #variants"),
        (fig5_miss_rate, "fig5: deadline miss rates (headline)"),
        (fig6_threshold_sweep, "fig6: accuracy-threshold sweep"),
        (fig7_arrival_robustness, "fig7: miss rate vs arrival burstiness (campaign)"),
        (fig8_adaptive_budgets, "fig8: online budget policies under burstiness"),
        (fig9_overload_control,
         "fig9: overload control — admission/shedding + closed-loop clients "
         "(writes BENCH_overload.json)"),
        (fig10_fault_tolerance,
         "fig10: fault tolerance — accelerator faults + variant-based "
         "graceful degradation (writes BENCH_faults.json)"),
        (fig11_dag_workloads,
         "fig11: DAG-structured workloads — layer-precedence scheduling "
         "(writes BENCH_dag.json)"),
        (fig12_fault_budgets,
         "fig12: fault-aware budget re-tightening + degraded-capacity "
         "admission (writes BENCH_fault_budgets.json)"),
        (table_storage, "storage overhead"),
        (ablation_backfill, "ablation: stage-2 backfill guard interpretations"),
        (bench_lm_serving, "beyond-paper: LM serving on mesh partitions"),
        (bench_campaign_throughput,
         "perf: SoA vs reference engine trials/sec (writes BENCH_campaign.json)"),
        (bench_sampler_efficiency,
         "perf: adaptive sampler trials saved at matched verdicts "
         "(writes BENCH_sampler.json)"),
        (bench_scheduler_round,
         "perf: deep-queue round kernels, rounds/sec vs NJ "
         "(writes BENCH_round.json)"),
        (bench_batch_trials,
         "perf: device-resident mega-batched trials vs the campaign path "
         "(writes BENCH_batch.json)"),
    ]:
        _section(title)
        rows = mod.run()
        for r in rows:
            print(json.dumps(r))
        all_claims += [(mod.__name__.split(".")[-1], *c) for c in mod.claims(rows)]

    _section("claim validation")
    n_ok = 0
    for src, claim, ok, detail in all_claims:
        status = "PASS" if ok else "FAIL"
        n_ok += bool(ok)
        print(f"[{status}] {src}: {claim} ({detail})")
    print(f"\n{n_ok}/{len(all_claims)} claims validated in {time.time()-t0:.0f}s")
    if n_ok < len(all_claims):
        if args.smoke:
            print("(smoke mode: claims informational at this scale; not failing)")
        else:
            sys.exit(1)


if __name__ == "__main__":
    main()
