"""On-chip benchmark of the device-resident trial engine.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
a model mix on a platform, with its latency tables, scheduler and
accuracy threshold) and a traffic mix (``traffic/<name>.json``: rates,
release processes, deadlines, horizon, faults, lanes per batch).  Each
per-layer metric is read by ``metrics/<name>.py``.  A new cell needs only
new files and a new entry.

A run:

1. builds the cell's offline plans through the program's
   ``Scenario.plans`` and the reference's own plans from the same files;
2. stages every batch of the cell's pool (the traffic's fixed set of
   seed batches, each of ``lanes`` trial seeds) and calls the device
   program of each distinct staged shape once, so every program the
   window can call is compiled (from the persistent cache) before it
   opens;
3. runs the window: whole passes over the pool, its batches in an order
   drawn from ``--seed``, each through the three stages of
   ``engine_batch.simulate_batch`` (host staging, the device loop, host
   assembly), timed one by one, until the pass that crosses
   ``--seconds`` ends;
4. with ``--trace 1``, reads the per-layer metrics from the host times
   of each stage, traces a few more batches with the profiler, and
   measures a cold compile;
5. compares a sample of the window's trials, drawn from the seed and
   including the lane with the most releases, field by field with the
   plain reference (``reference.py``), then prints one JSON line.

It refuses to run (exit 2, no result) without a TPU or with fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import devtrace  # noqa: E402
import reference  # noqa: E402

#: batches traced with the profiler in a ``--trace 1`` run, after the window
TRACE_BATCHES = 2
#: the limit of each number compared with the reference
LIMITS = {"lanes_differing": 0}


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------- loading ----


def _json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _json("configs", name + ".json")


def load_traffic(name: str) -> dict:
    return _json("traffic", name + ".json")


def load_metric(name: str):
    """The reader module of a per-layer metric, ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, bench: dict, name: str):
        found = [w for w in bench["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = found[0]
        self.name, self.chips = name, int(w["chips"])
        self.config = load_config(w["config"])
        self.traffic = load_traffic(w["traffic"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.lanes = int(self.traffic["lanes"])

    def pool(self) -> List[List[int]]:
        """The cell's batches: ``pool_batches`` sets of ``lanes`` trial
        seeds, the same for every run, so that every run does the same
        work (the padded width of a batch, which the device loop's time
        follows, varies from batch to batch)."""
        return [[int(s) for s in np.random.SeedSequence([self.traffic["pool_seed"], j])
                 .generate_state(self.lanes, np.uint32)]
                for j in range(int(self.traffic["pool_batches"]))]

    def order(self, seed: int) -> List[int]:
        """The order in which a run drawn from ``seed`` takes the pool."""
        n = int(self.traffic["pool_batches"])
        return np.random.default_rng([seed % 2**64, 0x0BDE]).permutation(n).tolist()


# ---------------------------------------------------------- program ----


class Program:
    """The system under test, set up for one cell."""

    def __init__(self, cell: Cell):
        from repro.core import engine_batch
        from repro.core.scheduler import make_scheduler
        from repro.core.simulator import make_arrival_process
        from repro.core.workload import Scenario, ScenarioEntry
        from repro.costmodel import dnn_zoo
        from repro.costmodel.maestro import PLATFORMS

        cfg, tr = cell.config, cell.traffic
        entries = tuple(
            ScenarioEntry(getattr(dnn_zoo, m["model"])(m["resolution"]), fps=e["fps"],
                          prob=e.get("prob", 1.0), arrival=make_arrival_process(e["arrival"]),
                          deadline=e.get("deadline_s"))
            for m, e in zip(cfg["models"], tr["entries"]))
        self.scenario = Scenario(tr["name"], entries, (cfg["platform"],),
                                 faults=tr.get("faults", "none"))
        self.plans, self.tasks = self.scenario.plans(
            PLATFORMS[cfg["platform"]], theta=cfg["theta"],
            enable_variants=cfg.get("enable_variants", True))
        self.scheduler = make_scheduler(cfg["scheduler"])
        self.horizon = float(tr["horizon_s"])
        self.faults = tr.get("faults", "none")
        self.eb = engine_batch
        self.run_trials = engine_batch._run_trials
        self.records: List[dict] = []  # host times of each batch run

    def stage(self, seeds):
        import jax

        with jax.enable_x64(True):
            return self.eb.stage_batch(self.plans, self.tasks, self.horizon, self.scheduler,
                                       seeds, faults=self.faults)

    def run(self, seeds):
        """One batch through the three stages of ``engine_batch.simulate_batch``,
        called as it calls them: host staging, the device loop to
        ``block_until_ready``, host assembly.  Each is timed on the host
        clock and annotated for the profiler (``bench.<stage>``); the
        times are appended to ``records``."""
        import jax

        eb, t = self.eb, [time.perf_counter()]
        with jax.enable_x64(True):
            with jax.profiler.TraceAnnotation("bench.stage"):
                staged = self.stage(seeds)
            t.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.loop"):
                out = jax.block_until_ready(self.run_trials(*staged.args, **staged.static))
            t.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.assemble"):
                results = eb.assemble_batch(out, staged, self.plans, self.tasks, self.horizon,
                                            self.scheduler)
            t.append(time.perf_counter())
        self.records.append({"stage_s": t[1] - t[0], "loop_s": t[2] - t[1],
                             "assemble_s": t[3] - t[2], "nr_pad": padded_width(staged),
                             "max_it": int(staged.args[7])})
        return results

    def warm(self, seeds) -> None:
        """Compile (or load from the cache) and run once the device
        program of a batch's staged shape, with an iteration bound of 0
        (the loop body then never runs)."""
        import jax

        staged = self.stage(seeds)
        args = list(staged.args)
        args[7] = np.zeros_like(args[7])  # max_it
        with jax.enable_x64(True):
            jax.block_until_ready(self.run_trials(*args, **staged.static))

    def compiles(self) -> int:
        """Device-loop programs compiled so far."""
        return self.run_trials._cache_size()


def padded_width(staged) -> int:
    """The padded event horizon ``NR`` of a staged batch."""
    return int(np.shape(staged.args[2])[-1])


def shape_key(staged) -> tuple:
    """What the device program is compiled for: argument shapes, dtypes
    and the static configuration."""
    import jax

    leaves = jax.tree_util.tree_leaves(staged.args)
    return (tuple((np.shape(x), str(np.asarray(x).dtype)) for x in leaves),
            tuple(sorted(staged.static.items())))


class Context:
    """What a per-layer metric's reader gets: the window's per-batch
    records, the reduced trace, and probes made after the window."""

    def __init__(self, program: Program, cell: Cell):
        self.program, self.cell = program, cell
        self.batches: List[dict] = []
        self.trace: Optional[dict] = None
        self.probes: Dict[str, float] = {}
        self.bucket_seeds: List[List[int]] = []  # one batch per shape, most frequent first


# ---------------------------------------------------------- running ----


def device_info(require_tpu: bool, chips: int) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def use_cache(root: str = ROOT) -> str:
    """The persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def warm_up(cell: Cell, program: Program, ctx: Context) -> List[List[int]]:
    """Stage every batch of the pool and warm the device program of each
    distinct staged shape.  Returns the pool."""
    pool = cell.pool()
    shapes: Dict[tuple, List[int]] = {}
    for j, seeds in enumerate(pool):
        staged = program.stage(seeds)
        shapes.setdefault(shape_key(staged), []).append(j)
    for js in sorted(shapes.values(), key=len, reverse=True):
        ctx.bucket_seeds.append(pool[js[0]])
        program.warm(pool[js[0]])
    return pool


def run_window(program: Program, pool, order: List[int], seconds: float):
    """Whole passes over the pool, taking it in ``order``, until one ends
    past ``seconds``, so that every run does the same work whatever its
    seed: ``(lanes, t0, t1, ends)`` with ``lanes`` the ``(seed,
    SimResult)`` of every trial run and ``ends`` each batch's end."""
    lanes, ends = [], []
    t0 = time.perf_counter()
    while True:
        seeds = pool[order[len(ends) % len(order)]]
        lanes.extend(zip(seeds, program.run(seeds)))
        t1 = time.perf_counter()
        ends.append(t1 - t0)
        if t1 - t0 >= seconds and len(ends) % len(order) == 0:
            return lanes, t0, t1, ends


def trace_batches(program: Program, pool, order: List[int], start: int) -> Optional[dict]:
    """Profile the next few batches after the window and reduce the trace."""
    session = devtrace.Session()
    try:
        for j in range(start, start + TRACE_BATCHES):
            program.run(pool[order[j % len(order)]])
    finally:
        events = session.stop()
    return devtrace.reduce(events)


def fingerprint_of(res: dict) -> tuple:
    """The reference result in the program's ``fingerprint()[1:]`` layout."""
    return (res["rounds"], res["busy"], res["busy_h"],
            {m: row for m, row in enumerate(res["models"])}, res["spans"])


def check_lanes(cell: Cell, lanes, seed: int, dtype=np.float64, log=None) -> Dict[str, int]:
    """Compare a sample of the window's trials with the reference.

    The sample is drawn from ``seed``: ``check_lanes - 1`` trials at
    random and the trial with the most releases."""
    n = min(int(cell.traffic["check_lanes"]), len(lanes))
    rel = [sum(s.released for s in r.per_model.values()) for _, r in lanes]
    longest = int(np.argmax(rel))
    rng = np.random.default_rng([seed % 2**64, 0xC4EC])
    others = [i for i in rng.permutation(len(lanes)).tolist() if i != longest][: n - 1]
    plans = reference.plans_for(cell.config, cell.traffic, dtype)
    differing = 0
    for i in [longest] + others:
        s, got = lanes[i]
        want = fingerprint_of(reference.simulate(cell.config, cell.traffic, s, dtype, plans))
        if got.fingerprint()[1:] != want:
            differing += 1
            if log is not None:
                log(f"lane seed {s}: program {got.fingerprint()[1:]} reference {want}")
    return {"lanes_differing": differing}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, root: str = ROOT, log=None) -> dict:
    """One run; returns the result line as a dict (``checks`` last)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = Cell(load_benchmark(root), workload)
    device = device_info(require_tpu, cell.chips)
    import jax

    use_cache(root)
    program = Program(cell)
    ctx = Context(program, cell)
    pool = warm_up(cell, program, ctx)
    order = cell.order(seed)
    before = program.compiles()
    n0 = len(program.records)
    lanes, t0, t1, ends = run_window(program, pool, order, seconds)
    compiled = program.compiles() - before
    batches = len(ends)
    ctx.batches = program.records[n0:n0 + batches]
    print(json.dumps({"workload": workload, "batches": batches, "window_s": t1 - t0,
                      "compiles_in_window": compiled, "batch_end_s": ends,
                      "batch_width": [b["nr_pad"] for b in ctx.batches],
                      "batch_stage_loop_assemble_s": [[b["stage_s"], b["loop_s"], b["assemble_s"]]
                                                      for b in ctx.batches]}),
          flush=True)
    if compiled:
        raise RuntimeError(f"{compiled} program(s) compiled inside the window")
    if trace:
        ctx.trace = trace_batches(program, pool, order, batches)
        if ctx.trace and ctx.trace["cut"]:
            log(f"trace: the profiler's buffer filled; traced window cut to "
                f"{ctx.trace['window_s']:.3f} s")
        for m in cell.per_layer:
            mod = load_metric(m["name"])
            if hasattr(mod, "collect"):
                mod.collect(ctx)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if ctx.trace:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]
    else:
        e2e = {"trials_per_s": len(lanes) / (t1 - t0), "setup_s": t0 - T_START}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    checks = check_lanes(cell, lanes, seed, log=log)
    line = {"correct": all(checks[k] <= LIMITS[k] for k in LIMITS),
            "attempted": len(lanes), "failed": checks["lanes_differing"],
            "metrics": metrics, "device": device}
    if trace and ctx.trace:
        line["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                             "idle_gaps": ctx.trace["idle_gaps"]}
    line["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]} for k in LIMITS}
    for k in LIMITS:
        log(f"check {k} = {checks[k]} (limit {LIMITS[k]})")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="On-chip benchmark of the trial engine.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
