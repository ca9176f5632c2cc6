"""The correctness check has to fail what it exists to catch.

On the CPU: the float32 reference put in the program's place, and a run
driven end to end (past the look for a chip) with the timed path broken
underneath.  On a TPU: the program's own float64 (``f64.NATIVE``, a
float32 pair there) at the cell's own size.  Run with
``python -m pytest benchmarks/chip/tests``."""

import numpy as np
import pytest

import bench
import control

BENCH = bench.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_float32_reference_in_the_programs_place_fails(name):
    rows = control.readings(name, [20260101], mode="float32")
    assert rows[0]["lanes_differing"] > 0


def test_native_float64_on_the_chip_fails():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("the chip's own float64 differs from binary64 only on a TPU")
    for row in control.readings(CELLS[0], [31, 32, 33], mode="native"):
        assert row["lanes_differing"] > 0


def _small(monkeypatch):
    """Every cell at 4 lanes, one pool batch, every lane checked."""
    load = bench.load_traffic

    def small(name):
        t = dict(load(name))
        t.update(lanes=4, pool_batches=1, check_lanes=4)
        return t

    monkeypatch.setattr(bench, "load_traffic", small)


def _unchanged_state(run):
    """The device loop returns its initial state: no event is popped."""
    def broken(*args, **kw):
        args = list(args)
        args[7] = np.zeros_like(args[7])  # max_it: the loop body never runs
        out = run(*args, **kw)
        return out._replace(drained=out.drained | True)
    return broken


def _half_batch(run):
    """Half of the lanes left out: their rows are the loop's initial state."""
    def broken(*args, **kw):
        import jax

        out = run(*args, **kw)
        idle = _unchanged_state(run)(*args, **kw)
        nb = int((np.asarray(args[5]) > 0).sum())
        return jax.tree_util.tree_map(lambda a, b: a.at[nb // 2:nb].set(b[nb // 2:nb]),
                                      out, idle)
    return broken


def _altered_answer(run):
    """One outcome altered where it is produced: request 0's miss flag."""
    def broken(*args, **kw):
        out = run(*args, **kw)
        return out._replace(missed=out.missed.at[:, 0].set(~out.missed[:, 0]))
    return broken


FAULTS = {"none": None, "unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_run_with_the_timed_path_broken_is_not_correct(monkeypatch, name, fault):
    from repro.core import engine_batch

    _small(monkeypatch)
    if FAULTS[fault] is not None:
        run = engine_batch._run_trials
        broken = FAULTS[fault](run)
        broken._cache_size = run._cache_size  # the harness counts the loop's compiles
        monkeypatch.setattr(engine_batch, "_run_trials", broken)
    line = bench.run_cell(name, 4242, 0.01, False, require_tpu=False, log=lambda m: None)
    assert line["correct"] is (fault == "none")
    assert list(line)[-1] == "checks"
    want = {"none": 0, "half_batch": 2}.get(fault, 4)
    assert line["checks"]["lanes_differing"]["value"] == want
