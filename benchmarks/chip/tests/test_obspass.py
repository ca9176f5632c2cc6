"""The readers of the program's spans and counters (``obspass``): the
reductions on synthetic recorder output and on a recorded chip excerpt,
the readers, and a traced run end to end on the CPU, with and without
the program's spans.  Run with ``python -m pytest benchmarks/chip/tests``."""

import json
import os
import sys

import pytest

import bench
import obspass

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = bench.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
READERS = ["loop_iters", "loop_us_per_iter", "lane_iter_share", "live_slot_share",
           "round_us_per_iter", "bind_us_per_iter", "assemble_replay_ms"]
DEV = "/device:TPU:0"


def _batch(i, loop_ns, replay_ns, **counters):
    spans = [(i, "batch", None, 0, loop_ns + replay_ns + 10),
             (i, "loop", "batch", 5, 5 + loop_ns),
             (i, "assemble.replay", "assemble", 5 + loop_ns, 5 + loop_ns + replay_ns)]
    return {"id": i, "spans": spans, "counters": counters}


def test_pass_metrics_on_synthetic_recorder_output():
    batches = [
        _batch(0, 3_000_000, 2_000_000, lanes=4, nr_pad=128, max_it=2000, iters_max=1000,
               iters_sum=3000, live_peak=32),
        _batch(1, 9_000_000, 4_000_000, lanes=2, nr_pad=256, max_it=4000, iters_max=2000,
               iters_sum=3000, live_peak=128),
    ]
    m = obspass.pass_metrics(batches)
    assert m["loop_iters"] == 1500 and m["max_it"] == 3000
    assert m["loop_us_per_iter"] == pytest.approx(12e6 * 1e-3 / 3000)
    assert m["lane_iter_share"] == pytest.approx(6000 / (4 * 1000 + 2 * 2000))
    assert m["live_slot_share"] == pytest.approx((32 / 128 + 128 / 256) / 2)
    assert m["assemble_replay_ms"] == pytest.approx(3.0)
    # a batch without the live_peak counter: no live share
    for b in batches:
        del b["counters"]["live_peak"]
    assert "live_slot_share" not in obspass.pass_metrics(batches)
    assert obspass.pass_metrics([]) == {}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_run_trials)/vmap()/while/body/round/stage2/add", "round"),
    ("jit(_run_trials)/vmap()/while/body/bind/jit(_where)/select_n", "bind"),
    ("jit(_run_trials)/vmap()/while/body/and", "other"),
    ("jit(_run_trials)/vmap()/while/body_pred/reduce_or", "cond"),
    ("jit(_run_trials)/vmap()/while/cond/reduce_or", "cond"),
    ("jit(_run_trials)/vmap()/while", None),
    ("jit(_run_trials)/vmap()/broadcast_in_dim", None),
    (None, None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert obspass.scope_of(op_name) == scope


def test_op_names_from_compiled_hlo_text():
    text = """
  %fusion.2 = f32[] fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/while/body/pop/add" source_file="x.py" source_line=3}
  ROOT %tuple.1 = (f32[]) tuple(%fusion.2), metadata={op_name="jit(f)/while/body/apply/y"}
  %copy.508 = f32[] copy(%fusion.2)
"""
    assert obspass.op_names(text) == {"fusion.2": "jit(f)/while/body/pop/add",
                                      "tuple.1": "jit(f)/while/body/apply/y"}


def test_scope_times_count_the_operations_of_the_loop():
    names = {"while.1": "j/while", "fusion.1": "j/while/body/round/stage1/add",
             "fusion.2": "j/while/body/bind/select_n", "fusion.3": "j/while/cond/lt",
             "copy.1": "j/broadcast", "fusion.4": "j/while/body/and"}
    ops = [("copy.1", 0, 5), ("while.1", 10, 100), ("fusion.1", 11, 20), ("fusion.2", 31, 7),
           ("copy.2", 38, 2), ("fusion.3", 40, 1), ("fusion.4", 41, 3), ("copy.1", 120, 5)]
    assert obspass.scope_times(ops, names) == {"round": 20, "bind": 7, "other": 5, "cond": 1}
    # a loop longer than the profiler's buffer has no while event: the same stretch
    assert obspass.scope_times([o for o in ops if o[0] != "while.1"], names) == \
        {"round": 20, "bind": 7, "other": 5, "cond": 1}
    assert obspass.scope_times([o for o in ops if o[0].startswith("copy")], names) == {}


def _iterations(n):
    """``n`` loop iterations: the condition, then round 30 ns and bind 10 ns."""
    names = {"fusion.3": "j/while/cond/reduce_or", "fusion.5": "j/while/body_pred/reduce_or",
             "fusion.1": "j/while/body/round/add", "fusion.2": "j/while/body/bind/select_n"}
    ops, t = [("copy.1", 0, 5)], 10
    for _ in range(n):
        for op, d in (("fusion.3", 1), ("fusion.1", 30), ("fusion.2", 10), ("fusion.5", 1)):
            ops.append((op, t, d))
            t += d
    return ops + [("copy.2", t + 5, 5)], names


def test_loop_iterations_and_the_stage_split_per_iteration():
    ops, names = _iterations(4)
    assert obspass.loop_iterations(ops, names) == 4
    split = obspass.stage_us_per_iter(ops, names)
    assert split == pytest.approx({"round": 0.030, "bind": 0.010, "cond": 0.002, "total": 0.042})
    # a stretch cut inside an iteration: the iterations it began
    assert obspass.loop_iterations(ops[:-3], names) == 4
    assert obspass.loop_iterations(ops[:1], names) == 0
    assert obspass.stage_us_per_iter(ops[:1], names) == {}


def test_weighted_takes_each_batch_at_its_shapes_split():
    narrow, wide = {"round": 60.0, "bind": 15.0}, {"round": 460.0, "bind": 22.0, "fault": 1.0}
    got = obspass.weighted([narrow, wide, narrow], [4000, 3000, 3000])
    assert got == pytest.approx({"round": (7000 * 60 + 3000 * 460) / 10000,
                                 "bind": (7000 * 15 + 3000 * 22) / 10000, "fault": 0.3})
    assert obspass.weighted([narrow, {}], [1, 1]) == {}  # a shape not read
    assert obspass.weighted([], []) == {}


def _ev(plane, line, name, s, e):
    return (plane, line, name, float(s), float(e - s))


def test_idle_gaps_are_cut_and_labelled_by_the_innermost_span():
    host = "/host:CPU"
    events = [
        _ev(host, "python", "engine.batch", 0, 100),
        _ev(host, "python", "engine.stage", 0, 10),
        _ev(host, "python", "engine.stage.pack", 2, 6),
        _ev(host, "python", "engine.loop", 10, 80),
        _ev(host, "python", "engine.assemble", 80, 100),
        _ev(host, "python", "engine.assemble.copy", 80, 88),
        _ev(host, "python", "engine.assemble.replay", 90, 99),
        _ev(host, "python", "bench.loop", 10, 80),
        _ev(DEV, "XLA Modules", "jit__run_trials", 12, 75),
    ]
    got = obspass.idle_gaps(events, top=10)
    assert got == [["assemble.replay", pytest.approx(9e-9)], ["assemble.copy", pytest.approx(8e-9)],
                   ["loop", pytest.approx(5e-9)], ["stage.pack", pytest.approx(4e-9)],
                   ["stage", pytest.approx(4e-9)], ["stage", pytest.approx(2e-9)],
                   ["loop", pytest.approx(2e-9)], ["assemble", pytest.approx(2e-9)],
                   ["assemble", pytest.approx(1e-9)]]
    assert sum(t for _, t in got) == pytest.approx(100e-9 - 63e-9)
    # the profiler's buffer filled in the loop: the window ends at the last device event
    events[-1] = _ev(DEV, "XLA Modules", "jit__run_trials", 12, 30)
    events[3] = _ev(host, "python", "engine.loop", 10, 30 + 2e8)
    events[0] = _ev(host, "python", "engine.batch", 0, 3e8)
    got = obspass.idle_gaps(events, top=10)
    assert sum(t for _, t in got) == pytest.approx(12e-9)
    assert obspass.idle_gaps([e for e in events if e[0] != DEV]) == []


def test_reductions_on_a_recorded_chip_excerpt():
    """The start of one batch of the saturation cell on one v5e chip: the
    host spans, the program's run and its first 400 device operations,
    named from the compiled program's metadata."""
    with open(os.path.join(HERE, "data", "obs_excerpt.json")) as f:
        d = json.load(f)
    ops = [tuple(o) for o in d["ops"]]
    got = obspass.scope_times(ops, d["op_names"])
    assert got == {"round": 58011.0, "bind": 14294.0, "pop": 8382.0, "other": 2648.0,
                   "apply": 900.0, "cond": 809.0, "drop": 4.0}
    assert set(got) <= set(obspass.SCOPES) | {"other", "cond"}
    (w,) = [o for o in ops if o[0].startswith("while")]
    inside = [o for o in ops if w[1] <= o[1] < w[1] + w[2] and o is not w]
    assert sum(got.values()) == sum(o[2] for o in inside)
    assert obspass.loop_iterations(ops, d["op_names"]) == 1  # the first iteration's start
    gaps = obspass.idle_gaps([tuple(e) for e in d["events"]])
    assert [label for label, _ in gaps] == ["assemble.copy", "assemble.replay", "stage.releases",
                                            "assemble.counts", "loop"]
    assert gaps[0][1] == pytest.approx(0.007844308)


class _Ctx:
    def __init__(self, found):
        self.probes = {"obs": found}


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_its_own_number(name):
    mod = bench.load_metric(name)
    found = {"metrics": {n: float(i) for i, n in enumerate(READERS)}}
    assert mod.read(_Ctx(found)) == float(READERS.index(name))
    assert mod.read(_Ctx(None)) is None
    assert mod.read(_Ctx({"metrics": {}})) is None
    entry = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == CELLS


def _small(monkeypatch):
    load = bench.load_traffic

    def small(name):
        t = dict(load(name))
        t.update(lanes=4, pool_batches=2, check_lanes=4)
        return t

    monkeypatch.setattr(bench, "load_traffic", small)


def test_a_traced_run_reports_the_programs_counters(monkeypatch, capsys):
    _small(monkeypatch)
    line = bench.run_cell(CELLS[0], 2**31 + 77, 0.01, True, require_tpu=False,
                          log=lambda m: None)
    assert line["correct"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # no device trace on the CPU: the two scope readings are left out
    assert set(READERS) - set(m) == {"round_us_per_iter", "bind_us_per_iter"}
    obs_line = [x for x in capsys.readouterr().err.splitlines() if x.startswith("obs ")]
    found = json.loads(obs_line[0][4:])
    assert m["loop_iters"] <= found["metrics"]["max_it"]
    assert 0 < m["lane_iter_share"] <= 1 and 0 < m["live_slot_share"] <= 1
    assert m["loop_us_per_iter"] > 0 and m["assemble_replay_ms"] > 0
    assert found["summary"]["counters"]["compiles"]["sum"] == 0
    assert found["trials_per_s"]["recorded"] > 0


def test_a_traced_run_of_a_program_without_spans_leaves_them_out(monkeypatch):
    """The benchmark laid over a program from before ``repro.core.obs``."""
    import repro.core
    from repro.core import engine_batch  # noqa: F401  (imported before obs is hidden)

    _small(monkeypatch)
    monkeypatch.delattr(repro.core, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    line = bench.run_cell(CELLS[0], 2**31 + 78, 0.01, True, require_tpu=False,
                          log=lambda m: None)
    assert line["correct"] and "loop_ms" in line["metrics"]
    assert not set(READERS) & set(line["metrics"])
