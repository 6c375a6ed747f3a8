"""The trace reduction, on a small trace recorded on one TPU v5 lite: three
rounds of a 1024x1024 bf16 matmul pair (``bench:compute``), a 10 ms host
sleep (``bench:host_wait``) and a small reduction (``bench:small``), inside
``bench:window``."""
from pathlib import Path

import pytest

from harness import trace

TRACE = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(TRACE))


def test_window_and_busy_time(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(0.036542011)
    # The device's clock in this trace runs about 1.1 ms ahead of the
    # host's, so the first round's matmuls (27.5 us) fall before the window's
    # start; two whole rounds and the first reduction (5 us) are in it.
    assert summary.busy_s[0] == pytest.approx(2 * (27.5e-6 + 5.0e-6) + 5.0e-6, rel=0.03)
    assert summary.busy_s[0] < summary.window_s


def test_ops_are_named_and_summed(summary):
    names = list(summary.op_s)
    assert {"fusion", "convolution_tanh_fusion", "multiply_reduce_fusion"} <= set(names)
    assert all(" " not in n and not n.startswith("%") for n in names)
    assert sum(summary.op_s.values()) == pytest.approx(summary.busy_s[0], rel=1e-6)
    assert summary.collective_s == 0


def test_idle_gaps_are_put_down_to_host_spans(summary):
    longest = summary.idle_gaps[:3]
    assert [g[0] for g in longest] == ["host_wait"] * 3
    assert all(0.0105 < g[1] < 0.0125 for g in longest)
    b = trace.breakdown(summary)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_interval_arithmetic():
    u = trace._union([(0, 2), (1, 3), (5, 6)])
    assert u == [[0, 3], [5, 6]]
    assert trace._minus(u, trace._union([(1, 2), (5, 7)])) == [[0, 1], [2, 3]]
    assert trace.op_name("%fusion.3 = bf16[2]{0} fusion(%a), kind=kLoop") == "fusion.3"
