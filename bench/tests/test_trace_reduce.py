"""The trace reduction, on a small trace recorded on a TPU v5e.

``data/small_trace.xplane.pb``: three calls of a jitted function of
three bf16 [512, 512] matmuls with tanh, profiled on one chip, each
call inside a host span ``host_step_<i>``.
"""
import os

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def trace():
    return T.read(T.find_xplane(DATA))


def test_the_chip_and_its_operations_are_found(trace):
    assert list(trace["devices"]) == ["/device:TPU:0"]
    ops = trace["devices"]["/device:TPU:0"]
    assert len(ops) == 12
    names = {name for _, _, name in ops}
    assert "convolution_tanh_fusion.1" in names
    assert all(e > s for s, e, _ in ops)
    assert any(name == "host_step_0" for _, _, name in trace["host"])


def test_busy_and_idle_add_up(trace):
    red = T.reduce(trace)
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(s for _, s in red["idle_gaps"])
    # ten longest gaps at most; with them the busy time fills the window
    assert red["busy_s"] + idle <= red["window_s"] * (1 + 1e-9)
    assert red["device_ops"][0][1] >= red["device_ops"][-1][1]
    assert sum(s for _, s in red["device_ops"]) >= red["busy_s"] * 0.999


def test_a_window_bounds_the_reduction(trace):
    assert T.window_of(trace["host"], "host_step_1") is not None
    ops = trace["devices"]["/device:TPU:0"]
    lo, hi = ops[4][0], ops[7][1]          # the second call's operations
    red = T.reduce(trace, lo, hi)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    whole = T.reduce(trace)
    assert red["busy_s"] < whole["busy_s"]
    assert all(isinstance(name, str) and name
               for name, _ in red["idle_gaps"])


def test_union_and_gaps():
    ivs = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (40, 50, "d")]
    busy = T.union(ivs, 2, 45)
    assert busy == [(2, 15), (20, 30), (40, 45)]
    assert T.gaps(busy, 0, 60) == [(0, 2), (15, 20), (30, 40), (45, 60)]
    assert T.op_name("%fusion.3 = bf16[8]{0} fusion(%x)") == "fusion.3"
    assert T.host_activity([(0, 100, "outer"), (10, 20, "inner")],
                           15) == "inner"
    assert T.host_activity([(0, 1, "x")], 5) == "none"


def test_nested_operations_count_once():
    # a loop op holding two body ops, then an op that outlasts the window
    ivs = [(0, 100, "while"), (10, 30, "body.a"), (40, 90, "body.b"),
           (100, 200, "after")]
    own = T.self_times(ivs, 0, 150)
    assert own == pytest.approx({"while": 30e-9, "body.a": 20e-9,
                                 "body.b": 50e-9, "after": 50e-9})
    red = T.reduce({"devices": {"/device:TPU:0": ivs}, "host": []}, 0, 150)
    assert sum(s for _, s in red["device_ops"]) == pytest.approx(
        red["busy_s"])
