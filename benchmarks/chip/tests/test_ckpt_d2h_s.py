"""The ``ckpt_d2h_s`` reader, on records laid in the span ring by hand."""
import collections
from types import SimpleNamespace

import pytest

from harness import core
from repro.core import spans

READER = core.load_module(core.HERE / "metrics" / "ckpt_d2h_s.py", "bench_metric_ckpt_d2h_s")


def _run(records, monkeypatch, saves=2):
    monkeypatch.setattr(spans, "_ring", collections.deque(records, maxlen=64))
    monkeypatch.setattr(spans, "_dropped", 0)
    # the window opens at 10 s on the ring's clock and lasts 20 s
    return SimpleNamespace(t_start=9.0, setup_s=1.0, window_s=20.0, facts={"saves": saves})


def _save(t, d2h):
    """One save's records from ``t``: a leaf copy per entry of ``d2h``."""
    recs = [("ckpt.snapshot", t, t + 0.001, 300)]
    for i, w in enumerate(d2h):
        t0 = t + 0.01 + i
        recs += [("ckpt.d2h", t0, t0 + w, 100), ("ckpt.serialize", t0 + w, t0 + w + 0.1, 100)]
    end = t + 0.01 + len(d2h)
    return recs + [("ckpt.finalize", end, end + 0.02, 0)]


def test_reads_the_wait_per_save(monkeypatch):
    # one save before the window, which is not counted; two in it
    recs = _save(2.0, [0.9, 0.9]) + _save(12.0, [0.2, 0.1, 0.0]) + _save(20.0, [0.3, 0.0, 0.0])
    assert READER.read(_run(recs, monkeypatch)) == pytest.approx((0.3 + 0.3) / 2)


def test_reads_none_without_d2h_records(monkeypatch):
    recs = [r for r in _save(12.0, [0.2]) + _save(20.0, [0.3]) if r[0] != "ckpt.d2h"]
    assert READER.read(_run(recs, monkeypatch)) is None
