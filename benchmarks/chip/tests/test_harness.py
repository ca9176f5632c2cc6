"""The harness on the CPU: loading by name, traffic against the catalog,
the rate over whole batches, the trace reduction, and refusal without a
chip.  Run with ``python -m pytest benchmarks/chip/tests``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench
import devtrace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
BENCH = bench.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_its_files_by_name(name):
    cell = bench.Cell(BENCH, name)
    assert cell.config["models"] and cell.traffic["entries"]
    assert len(cell.config["models"]) == len(cell.traffic["entries"])
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "trials_per_s"}
    for m in cell.per_layer:
        assert callable(bench.load_metric(m["name"]).read)


def test_every_entry_names_existing_files():
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert os.path.isfile(os.path.join(bench.HERE, "traffic", w["traffic"] + ".json"))
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(bench.HERE, "metrics", m["name"] + ".py"))


def _fault_parts(spec):
    """A fault spec as its parsed parts: ``[(kind, {key: value})]``, none
    for ``"none"``."""
    import reference

    if spec in (None, "", "none"):
        return []
    return [reference.parse_spec(part) for part in spec.split("+")]


def check_catalogued(traffic):
    """The catalog scenario a traffic file declares it was written from
    (``catalog``, with ``catalog_arrival`` given to ``Scenario.plans``),
    and an ``AssertionError`` unless the traffic's fault spec is the
    catalog's, part by part: a fault wave is run at its own times."""
    from repro.core.workload import get_scenario

    for key in ("catalog", "catalog_arrival"):
        assert key in traffic, f"traffic {traffic.get('name')!r} has no {key!r} key"
    sc = get_scenario(traffic["catalog"])
    got, want = _fault_parts(traffic.get("faults")), _fault_parts(sc.faults)
    assert got == want, f"fault parts {got} against the catalog's {want}"
    return sc, traffic["catalog_arrival"]


@pytest.mark.parametrize("name", CELLS)
def test_traffic_builds_the_catalogued_scenario(name):
    from repro.costmodel.maestro import PLATFORMS

    cell = bench.Cell(BENCH, name)
    prog = bench.Program(cell)
    sc, arrival = check_catalogued(cell.traffic)
    plans, tasks = sc.plans(PLATFORMS[cell.config["platform"]], theta=cell.config["theta"],
                            arrival=arrival)
    assert tasks == prog.tasks
    for p, q, m in zip(plans, prog.plans, cell.config["models"]):
        assert (p.model.name, len(p.model.layers), p.deadline) == \
            (q.model.name, len(q.model.layers), q.deadline)
        # the layer graph the reference reads is the catalog's (a chain has none)
        graph = None if p.dag is None else [list(ps) for ps in p.dag.preds]
        assert graph == m.get("preds")
        assert q.dag == p.dag
        assert (p.lat == q.lat).all() and (p.vdl_rel == q.vdl_rel).all()
        assert (p.lat_var == q.lat_var).all()
        assert {l: v.loss for l, v in p.variants.items()} == \
            {l: v.loss for l, v in q.variants.items()}


WAVE = "throttle(acc=0,start=0.2,duration=0.5,factor=3.0)+down(acc=1,start=0.7,duration=0.5)"


@pytest.fixture
def catalog(monkeypatch):
    """Two catalog scenarios: ``wave`` with ``WAVE``, ``calm`` with no faults."""
    from repro.core import workload

    for name, faults in (("wave", WAVE), ("calm", None)):
        monkeypatch.setitem(workload.FAULT_SCENARIOS, name,
                            workload.Scenario(name, (), (), faults=faults))


def _traffic(catalog, faults):
    return {"name": "t", "catalog": catalog, "catalog_arrival": "poisson", "faults": faults}


@pytest.mark.parametrize("name,faults", [
    ("wave", WAVE),
    ("wave", WAVE.replace("start=0.7,duration=0.5", "duration=0.5,start=0.7")),
    ("calm", "none"),
    ("calm", None),
])
def test_the_catalog_check_takes_the_catalogs_wave(catalog, name, faults):
    sc, arrival = check_catalogued(_traffic(name, faults))
    assert sc.name == name and arrival == "poisson"


@pytest.mark.parametrize("name,faults", [
    ("wave", WAVE.replace("start=0.2,duration=0.5", "start=0.1,duration=0.25")),  # retimed
    ("wave", WAVE.replace("factor=3.0", "factor=2.0")),  # another throttle factor
    ("wave", WAVE.replace("acc=0", "acc=2")),  # another accelerator
    ("wave", WAVE.split("+")[0]),  # a part missing
    ("wave", WAVE.replace("down(", "throttle(")),  # another kind of fault
    ("wave", WAVE.replace("factor=3.0", "factor=3.0,retighten=true")),  # a key more
    ("wave", "none"),
    ("calm", "down(acc=0,start=0.1,duration=0.2)"),  # faults where the catalog has none
])
def test_the_catalog_check_refuses_another_wave(catalog, name, faults):
    with pytest.raises(AssertionError):
        check_catalogued(_traffic(name, faults))


@pytest.mark.parametrize("key", ["catalog", "catalog_arrival"])
def test_a_traffic_that_declares_no_catalog_fails_by_name(catalog, key):
    traffic = _traffic("calm", "none")
    del traffic[key]
    with pytest.raises(AssertionError, match=repr(key)):
        check_catalogued(traffic)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_make_config_writes_the_committed_configuration(config):
    """``make_config.tables`` gives each configuration's committed tables,
    with ``preds`` exactly for the models whose layers form a graph."""
    import make_config
    from repro.costmodel import dnn_zoo

    with open(os.path.join(ROOT, config["file"])) as f:
        committed = json.load(f)
    accs, models = make_config.tables(committed["platform"],
                                      [(m["model"], m["resolution"]) for m in committed["models"]])
    assert accs == committed["accelerators"]
    assert json.loads(json.dumps(models)) == committed["models"]
    for m in committed["models"]:
        dag = getattr(dnn_zoo, m["model"])(m["resolution"]).dag
        assert ("preds" in m) == (dag is not None and not dag.is_linear)


@pytest.mark.parametrize("name", CELLS)
def test_reference_offline_stage_equals_the_programs(name):
    """The configuration's tables and the reference's own Algorithm 1
    and variant choice give the program's plans, bit for bit."""
    import reference

    cell = bench.Cell(BENCH, name)
    prog = bench.Program(cell)
    for p, q in zip(prog.plans, reference.plans_for(cell.config, cell.traffic)):
        assert (p.lat == q.lat).all() and (p.lat_var == q.lat_var).all()
        assert (p.vdl_rel == q.vdl_rel).all() and (p.remaining_min == q.rm).all()
        assert {l: v.loss for l, v in p.variants.items()} == q.loss


def test_the_pool_is_fixed_and_the_seed_sets_its_order():
    cell = bench.Cell(BENCH, CELLS[0])
    pool = cell.pool()
    assert pool == cell.pool() and len(pool) == cell.traffic["pool_batches"]
    assert all(len(b) == cell.lanes for b in pool)
    assert len({s for b in pool for s in b}) == cell.lanes * len(pool)
    big = 2**31 + 12345
    assert cell.order(big) == cell.order(big)
    assert sorted(cell.order(big)) == list(range(len(pool)))
    assert len({tuple(cell.order(s)) for s in range(big, big + 8)}) > 1


def test_a_batch_runs_the_three_stages_of_the_entry_point():
    """``Program.run`` gives the trials ``simulate_batch`` gives, and
    records each stage's host time."""
    cell = bench.Cell(BENCH, CELLS[0])
    prog = bench.Program(cell)
    seeds = cell.pool()[0][:4]
    got = prog.run(seeds)
    want = prog.eb.simulate_batch(prog.plans, prog.tasks, prog.horizon, prog.scheduler, seeds,
                                  faults=prog.faults)
    assert [r.fingerprint() for r in got] == [r.fingerprint() for r in want]
    (rec,) = prog.records
    assert all(rec[k] > 0 for k in ("stage_s", "loop_s", "assemble_s", "nr_pad", "max_it"))


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class _FakeProgram:
    def __init__(self, clock, step):
        self.clock, self.step, self.calls = clock, step, []

    def run(self, seeds):
        self.calls.append(list(seeds))
        self.clock.t += self.step
        return [object()] * len(seeds)


@pytest.mark.parametrize("seconds,step,batches", [(1.0, 0.25, 4), (0.75, 0.25, 4), (0.1, 0.5, 2),
                                                   (1.1, 0.25, 6)])
def test_window_counts_whole_passes_up_to_the_one_that_crosses(monkeypatch, seconds, step,
                                                               batches):
    clock = _Clock()
    monkeypatch.setattr(bench.time, "perf_counter", clock)
    prog = _FakeProgram(clock, step)
    pool = [[1, 2], [3, 4]]
    lanes, t0, t1, ends = bench.run_window(prog, pool, [1, 0], seconds)
    assert len(ends) == batches and ends[-1] == t1 - t0
    assert len(prog.calls) == batches and len(lanes) == 2 * batches
    assert t1 - t0 == pytest.approx(batches * step)
    # trials per second: every trial of every whole batch over the window
    assert len(lanes) / (t1 - t0) == pytest.approx(2 / step)
    # the pool is taken in the given order, and cycled
    assert prog.calls == [pool[[1, 0][k % 2]] for k in range(batches)]


def _ev(plane, line, name, s, e):
    return (plane, line, name, float(s), float(e - s))


def test_trace_reduction_on_a_small_synthetic_trace():
    dev = "/device:TPU:0"
    events = [
        _ev("/host:CPU", "python", "bench.stage", 0, 10),
        _ev("/host:CPU", "python", "bench.loop", 10, 100),
        _ev("/host:CPU", "python", "bench.assemble", 100, 110),
        _ev(dev, "XLA Ops", "fusion.1", 12, 50),
        _ev(dev, "XLA Ops", "while.2", 40, 95),
        _ev(dev, "XLA Ops", "copy.3", 105, 107),
    ]
    r = devtrace.reduce(events)  # no module events: busy is the union of the ops
    assert r["window_s"] == pytest.approx(110e-9)
    assert r["busy_s"] == pytest.approx(85e-9)
    assert r["device_ops"] == [["while.2", pytest.approx(55e-9)],
                               ["fusion.1", pytest.approx(38e-9)],
                               ["copy.3", pytest.approx(2e-9)]]
    assert r["idle_gaps"] == [["stage", pytest.approx(12e-9)],
                              ["assemble", pytest.approx(10e-9)],
                              ["assemble", pytest.approx(3e-9)]]
    assert devtrace.reduce([e for e in events if e[0] != dev]) is None
    # module executions, where present, are what the device was busy with
    mods = [_ev(dev, "XLA Modules", "jit_a", 12, 95), _ev(dev, "XLA Modules", "jit_b", 104, 108)]
    r = devtrace.reduce(events + mods)
    assert r["busy_s"] == pytest.approx(87e-9)
    assert r["device_ops"][0] == ["while.2", pytest.approx(55e-9)]
    assert r["idle_gaps"] == [["stage", pytest.approx(12e-9)], ["loop", pytest.approx(9e-9)],
                              ["assemble", pytest.approx(2e-9)]]


def test_trace_reduction_ends_the_window_where_the_buffer_filled():
    dev = "/device:TPU:0"
    events = [
        _ev("/host:CPU", "python", "bench.stage", 0, 10),
        _ev("/host:CPU", "python", "bench.loop", 10, 1e9),
        _ev("/host:CPU", "python", "bench.assemble", 1e9, 1e9 + 10),
        _ev(dev, "XLA Modules", "jit_a", 12, 5e8),  # nothing after 0.5 s
    ]
    r = devtrace.reduce(events)
    assert r["cut"] and r["window_s"] == pytest.approx(0.5)
    assert r["busy_s"] == pytest.approx((5e8 - 12) * 1e-9)
    assert r["idle_gaps"] == [["stage", pytest.approx(12e-9)]]
    # a device that ends within the loop span's last 0.1 s is whole
    events[-1] = _ev(dev, "XLA Modules", "jit_a", 12, 1e9 - 5e7)
    r = devtrace.reduce(events)
    assert not r["cut"] and r["window_s"] == pytest.approx((1e9 + 10) * 1e-9)


def test_trace_flattening_keeps_spans_modules_and_capped_ops(monkeypatch):
    class Ev:
        def __init__(self, name, s, d):
            self.name, self.start_ns, self.duration_ns = name, s, d

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Data:
        planes = [
            Plane("/host:CPU", [Line("python", [Ev("bench.loop", 0, 10), Ev("other", 1, 2)])]),
            Plane("/device:TPU:0", [
                Line("XLA Modules", [Ev("jit_run(1)", 1, 8)]),
                Line("XLA Ops", [Ev("%while.1 = (s32[2]) while(...)", 1, 8),
                                 Ev("%fusion.2 = f32[] fusion()", 2, 1),
                                 Ev("%fusion.3 = f32[] fusion()", 3, 1)]),
                Line("Steps", [Ev("0", 1, 8)])]),
        ]

    monkeypatch.setattr(devtrace, "MAX_OPS", 2)
    got = devtrace.flatten(Data())
    assert got == [("/host:CPU", "python", "bench.loop", 0.0, 10.0),
                   ("/device:TPU:0", "XLA Modules", "jit_run(1)", 1.0, 8.0),
                   ("/device:TPU:0", "XLA Ops", "%while.1", 1.0, 8.0),
                   ("/device:TPU:0", "XLA Ops", "%fusion.2", 2.0, 1.0)]


def test_trace_reduction_on_a_recorded_chip_trace():
    """An excerpt of a trace recorded on one v5e chip: one batch of the
    saturation cell, the host span and the first and last device ops."""
    with open(os.path.join(HERE, "data", "trace_excerpt.json")) as f:
        events = [tuple(e) for e in json.load(f)["events"]]
    r = devtrace.reduce(events)
    assert r["window_s"] == pytest.approx(0.558476323)
    assert r["busy_s"] == pytest.approx(0.534091328)  # the one program execution
    assert r["device_ops"][0] == ["%while.458", pytest.approx(0.534076141)]
    idle = sum(t for _, t in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-12
    assert all(label in ("stage", "loop", "assemble", "other") for label, _ in r["idle_gaps"])
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= devtrace.TOP


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(["benchmarks/chip/bench.py", "--workload", CELLS[0], "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"), tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(["benchmarks/chip/bench.py", "--workload", CELLS[0], "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
    # past the look for a chip, the program itself is missing
    r = _run(["-c", "import sys; sys.path.insert(0, 'benchmarks/chip'); import bench; "
              f"bench.run_cell({CELLS[0]!r}, 1, 1.0, False, require_tpu=False, "
              f"root={str(tmp_path)!r})"], tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
