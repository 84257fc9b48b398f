"""The trace reducer: interval arithmetic on a hand-made trace, and the
whole reduction on a trace recorded on a TPU v5e."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import xplane  # noqa: E402

# one 2-generation search of cardio.robust_k8 on a TPU v5e, recorded with
# bench/tools/record_trace.py
RECORDED = BENCH / "tests" / "data" / "trace.xplane.pb.gz"
D0, D1 = "/device:TPU:0", "/device:TPU:1"


def hand_made():
    # window [100, 200]; chip 0 busy [90, 120] ∪ [110, 130] ∪ [150, 160]
    # inside a loop over [95, 135] (45 ns inside the window), chip 1 busy
    # [100, 200]
    ops = {D0: [("fusion.1", 90, 120), ("pop_generation_kernel.3", 110, 130),
                ("pop_generation_kernel.3", 150, 160), ("while.2", 95, 135)],
           D1: [("fusion.2", 100, 200)]}
    modules = {D0: [("jit__run_segment", 90, 130),
                    ("jit__run_segment", 150, 160)]}
    host = [("bench.window", 100, 200), ("serve.step", 100, 140),
            ("serve.submit", 160, 200)]
    return xplane.Trace((100, 200), ops, modules, host)


def test_busy_is_the_union_of_operations_within_the_window():
    tr = hand_made()
    assert xplane.busy_ns(tr, D0) == 45
    assert xplane.busy_ns(tr, D1) == 100
    assert xplane.busy_s(tr) == pytest.approx(72.5e-9)
    assert xplane.idle_share(tr) == pytest.approx(27.5)


def test_gaps_and_idle_time():
    tr = hand_made()
    assert xplane.gaps(tr, D0) == [(135, 150), (160, 200)]
    assert xplane.idle_ns(tr, D0, 130, 150) == 15
    assert xplane.idle_ns(tr, D0, 120, 155) == 15


def test_op_time_by_name_pattern():
    tr = hand_made()
    assert xplane.op_ns(tr, r"^pop_generation_kernel\b") == 30
    assert xplane.op_ns(tr, r"^pop_generation_kernel\b", D1) == 0
    assert xplane.module_spans(tr, "_run_segment", D0) == [(100, 130),
                                                          (150, 160)]


def test_breakdown_names_gaps_by_the_open_host_span():
    bd = xplane.breakdown(hand_made(), ("serve.step", "serve.submit"))
    assert bd["device_ops"] == [["pop_generation_kernel.3",
                                 pytest.approx(30e-9)],
                                ["fusion.1", pytest.approx(20e-9)]]
    assert bd["idle_gaps"] == [["serve.submit", pytest.approx(40e-9)],
                               ["serve.step", pytest.approx(15e-9)]]


def test_short_names_and_a_truncated_trace():
    assert xplane.short("%fusion.5 = s32[25,226]{1,0} fusion(%a)") == (
        "fusion.5")
    tr = hand_made()
    assert xplane.complete(tr)
    # a program that runs on after chip 0's last recorded operation
    tr.modules[D0].append(("jit__run_segment", 180, 199))
    assert not xplane.complete(tr)


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(str(RECORDED))


def test_recorded_trace_has_one_chip_and_the_window(recorded):
    assert recorded.devices == [D0]
    assert 0 < recorded.window_s < 1
    assert any(h[0] == "search.run" for h in recorded.host)
    assert xplane.complete(recorded)


def test_recorded_trace_busy_time_and_kernel_time(recorded):
    busy = xplane.busy_s(recorded)
    assert 0 < busy < recorded.window_s
    assert 0 < xplane.idle_share(recorded) < 100
    idle = sum(e - s for s, e in xplane.gaps(recorded, D0)) * 1e-9
    assert busy + idle == pytest.approx(recorded.window_s)
    kernel = xplane.op_ns(recorded, r"^pop_generation_kernel\b") * 1e-9
    assert 0 < kernel < busy


def test_recorded_trace_breakdown(recorded):
    bd = xplane.breakdown(recorded, ("search.run", "search.front"))
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    assert not any(xplane.CONTAINERS.match(n) for n, _ in bd["device_ops"])
    assert {n for n, _ in bd["idle_gaps"]} <= {"search.run", "search.front",
                                              "other"}
