"""Device-idle shares of the program's own phase spans.

``ServeEngine.step`` writes four consecutive spans (``engine.admit``,
``engine.prefill``, ``engine.decode``, ``engine.sample``) inside the
benchmark's ``engine.step``; ``Trainer.run`` writes ``trainer.feed``,
``trainer.compute`` and, when it saves or waits for a save,
``trainer.checkpoint`` inside ``trainer.step``.  A phase's idle is the
time inside its spans in the window in which no op ran on the device;
its share is taken over the denominator of the matching ``device_idle``
metric, so a cell's phases sum to that metric less the idle between
spans.  A program that writes no phase spans reads None.
"""
from __future__ import annotations

from bench import trace


def _written(rec: dict, name: str) -> bool:
    lo, hi = rec["window"]
    return any(n == name and e > lo and s < hi for n, s, e in rec["spans"])


def _idle_s(rec: dict, phase: str) -> float:
    busy, inside = trace.busy_within(rec, phase)
    return inside - busy


def engine_idle(run, phase: str) -> float | None:
    """% of the time inside ``engine.step`` spans that the device idled
    inside ``phase`` spans."""
    rec = run.trace
    if rec is None or not _written(rec, "engine.admit"):
        return None
    _, inside = trace.busy_within(rec, "engine.step")
    if inside <= 0:
        return None
    return 100.0 * _idle_s(rec, phase) / inside


def trainer_idle(run, phase: str) -> float | None:
    """% of the window that the device idled inside ``phase`` spans; 0
    when the phase wrote no span there (no save fell in the window)."""
    rec = run.trace
    if rec is None or not _written(rec, "trainer.feed") \
            or trace.window_s(rec) <= 0:
        return None
    return 100.0 * _idle_s(rec, phase) / trace.window_s(rec)
