"""Spans and counters of the batch engine.

:func:`span` always opens ``jax.profiler.TraceAnnotation("engine." +
name)``, so the span lands in any profiler trace on the profiler's own
host clock, beside the device events.  While a recorder is active
(``with record() as rec:``) spans and counters are also kept in memory,
one dict per batch::

    with obs.record() as rec:
        simulate_batch(plans, tasks, horizon, scheduler, seeds)
    rec.batches   # [{"id": 0, "spans": [Span, ...], "counters": {...}}]
    rec.summary() # per span: calls, total and self ms; per counter: sum, mean, max

Recorded times are ``time.time_ns()``, the wall clock the profiler
stamps its host events with, so a recorded span and its annotation in a
trace agree.

A batch is opened by ``engine_batch.stage_batch`` (:func:`open_batch`);
a span or counter names its batch, or belongs to the batch opened last.
The recorder is the only switch: no file, logging handler or
environment variable.  One recorder at a time, on one thread.

On the device, each stage of the loop body (``engine_batch._run_trials``)
runs under :func:`scope`, a ``jax.named_scope`` named from
:data:`SCOPES`, so every device operation carries its stage in its op
name (``.../while/body/<stage>/...``).
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

import jax

PREFIX = "engine."
#: the stages of the device loop's body, in the order they run
SCOPES = ("pop", "bind", "counters", "fault", "epoch", "drop", "round", "apply")


class Span(NamedTuple):
    """One closed span of a recorded batch (names without ``PREFIX``)."""

    batch: int
    name: str
    parent: Optional[str]  # the span open around it, None at the top
    start_ns: int
    end_ns: int


class Recorder:
    """Spans and counters of the batches run while it is active."""

    def __init__(self):
        self.batches: List[dict] = []
        self._open: List[str] = []  # names of the spans open now

    def _batch(self, batch: Optional[int]) -> Optional[dict]:
        if batch is None:
            return self.batches[-1] if self.batches else None
        return self.batches[batch]

    def summary(self) -> Dict[str, dict]:
        """Per span name: ``calls``, ``total_ms`` and ``self_ms`` (the
        span's time less what its child spans cover); per counter:
        ``sum``, ``mean`` and ``max`` over the batches that set it."""
        spans: Dict[str, dict] = {}
        counters: Dict[str, List[float]] = {}
        for b in self.batches:
            for s in b["spans"]:
                inner = sum(c.end_ns - c.start_ns for c in b["spans"]
                            if c.parent == s.name and s.start_ns <= c.start_ns
                            and c.end_ns <= s.end_ns and c is not s)
                row = spans.setdefault(s.name, {"calls": 0, "total_ms": 0.0, "self_ms": 0.0})
                row["calls"] += 1
                row["total_ms"] += (s.end_ns - s.start_ns) * 1e-6
                row["self_ms"] += (s.end_ns - s.start_ns - inner) * 1e-6
            for k, v in b["counters"].items():
                counters.setdefault(k, []).append(v)
        return {"spans": spans,
                "counters": {k: {"sum": sum(v), "mean": sum(v) / len(v), "max": max(v)}
                             for k, v in counters.items()}}


_active: Optional[Recorder] = None


def active() -> Optional[Recorder]:
    """The recorder in use, or None."""
    return _active


@contextlib.contextmanager
def record() -> Iterator[Recorder]:
    """Keep the spans and counters of every batch run inside the block."""
    global _active
    if _active is not None:
        raise RuntimeError("a recorder is already active")
    _active = Recorder()
    try:
        yield _active
    finally:
        _active = None


def open_batch() -> Optional[int]:
    """Start a batch in the active recorder; its id, or None without one."""
    if _active is None:
        return None
    _active.batches.append({"id": len(_active.batches), "spans": [], "counters": {}})
    return len(_active.batches) - 1


@contextlib.contextmanager
def span(name: str, batch: Optional[int] = None) -> Iterator[None]:
    """Annotate ``engine.<name>`` for the profiler and, while a recorder
    is active, keep it in ``batch`` (the batch opened last when None,
    as it stands when the span closes)."""
    rec = _active
    with jax.profiler.TraceAnnotation(PREFIX + name):
        if rec is None:
            yield
            return
        parent = rec._open[-1] if rec._open else None
        rec._open.append(name)
        t0 = time.time_ns()
        try:
            yield
        finally:
            t1 = time.time_ns()
            rec._open.pop()
            b = rec._batch(batch)
            if b is not None:
                b["spans"].append(Span(b["id"], name, parent, t0, t1))


def count(name: str, value, batch: Optional[int] = None) -> None:
    """Set a counter of ``batch`` (the batch opened last when None)."""
    if _active is None:
        return
    b = _active._batch(batch)
    if b is not None:
        b["counters"][name] = value


def scope(name: str):
    """The ``jax.named_scope`` of the loop stage ``name`` (one of
    :data:`SCOPES`)."""
    if name not in SCOPES:
        raise ValueError(f"not a loop stage: {name!r}")
    return jax.named_scope(name)
