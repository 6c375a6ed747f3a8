"""The program's own spans, as ``repro.core.spans`` records them, for the
per-layer metrics that split a save, a restore or a cold start.

A record is ``(name, t0, t1, nbytes)`` on ``time.perf_counter()``, the clock
of the run's window, which opens at ``run.t_start + run.setup_s`` and lasts
``run.window_s``. A reader gets None where there is nothing to read: a
program that records no spans, a ring that let go of records from inside
the window, or no record of the names asked for.
"""
from __future__ import annotations

from typing import Optional

FETCH = {"restore.fetch"}
HOST = {"restore.crc", "restore.place"}


def _from_window_start(run) -> Optional[list]:
    try:
        from repro.core import spans
    except ImportError:
        return None
    if run.setup_s is None or run.window_s is None:
        return None
    w0 = run.t_start + run.setup_s
    recs = spans.records()
    # records enter the ring as they end: what it let go ended before the
    # oldest it holds, so nothing of the window is lost while that one
    # ended before the window opened
    if spans.dropped() and (not recs or recs[0][2] >= w0):
        return None
    return [r for r in recs if r[1] >= w0]


def window_records(run) -> Optional[list]:
    """Records that start inside the window."""
    recs = _from_window_start(run)
    if recs is None:
        return None
    w1 = run.t_start + run.setup_s + run.window_s
    return [r for r in recs if r[1] < w1]


def save_records(run) -> Optional[list]:
    """Records from the window's start to the end of the last of its
    ``saves`` saves, so that the disk thread's spans of a save count
    though they run past the window's end. None unless every save ended."""
    recs = _from_window_start(run)
    n = run.facts.get("saves")
    if recs is None or not n:
        return None
    ends = sorted(t1 for name, _, t1, _ in recs if name == "ckpt.finalize")
    if len(ends) < n:
        return None
    return [r for r in recs if r[1] <= ends[n - 1]]


def per_event(recs: Optional[list], names: set, events) -> Optional[float]:
    """Total time of the records named ``names`` over ``events``, in s."""
    if recs is None or not events or not any(r[0] in names for r in recs):
        return None
    return sum(t1 - t0 for name, t0, t1, _ in recs if name in names) / events
