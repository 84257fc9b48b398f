"""The serve-span readers: the segment gap put down to the program's host
spans, and the queue wait by Little's law, on hand-made traces."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import run  # noqa: E402
import spans  # noqa: E402
import xplane  # noqa: E402

D0, D1 = "/device:TPU:0", "/device:TPU:1"
SPAN_METRICS = {"serve.admit": "admit_gap_ms.serve",
                "serve.sync": "sync_gap_ms.serve",
                "serve.retire": "retire_gap_ms.serve"}


def reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py").read


def stepped(host):
    # window [0, 200]; chip 0 runs segments [0, 10], [50, 60], [100, 110]
    # and one admission operation [30, 35]; chip 1 the segments alone
    segs = [("jit__run_segment", 0, 10), ("jit__run_segment", 50, 60),
            ("jit__run_segment", 100, 110)]
    ops = {D0: [("fusion.1", 0, 10), ("fusion.2", 30, 35),
                ("fusion.1", 50, 60), ("fusion.1", 100, 110)],
           D1: [("fusion.1", 0, 10), ("fusion.1", 50, 60),
                ("fusion.1", 100, 110)]}
    modules = {D0: list(segs), D1: list(segs)}
    return xplane.Trace((0, 200), ops, modules,
                        [("bench.window", 0, 200)] + host)


# step 1, gap [10, 50]: the read waits past the segment's end, then the
# retirement, unspanned host work, the admission (busy [30, 35] on chip 0),
# the dispatch; step 2, gap [60, 100]: a read that straddles the segment's
# end, and an admission that runs into the next segment
STEPS = [("serve.sync", 5, 12), ("serve.retire", 12, 20),
         ("serve.admit", 25, 45), ("serve.sync", 55, 70),
         ("serve.retire", 70, 72), ("serve.retire", 72, 80),
         ("serve.admit", 95, 105)]


def test_idle_split_across_span_names():
    tr = stepped(STEPS)
    # per chip, summed over both gaps ÷ 2; chip 1 has no busy admission
    want = {"serve.sync": ((2 + 10) / 2, (2 + 10) / 2),
            "serve.retire": ((8 + 10) / 2, (8 + 10) / 2),
            "serve.admit": ((15 + 5) / 2, (20 + 5) / 2)}
    for span, (c0, c1) in want.items():
        got = reader(SPAN_METRICS[span])({"trace": tr})
        assert got == pytest.approx((c0 + c1) / 2 * 1e-6), span
    # together they are part of the whole gap: the rest is unspanned
    whole = reader("segment_gap_ms.serve")({"trace": tr})
    assert whole == pytest.approx(((35 + 40) / 2 + (40 + 40) / 2) / 2 * 1e-6)
    parts = sum(reader(m)({"trace": tr}) for m in SPAN_METRICS.values())
    # unspanned: [20, 25] and [45, 50], then [80, 95], on either chip
    assert parts == pytest.approx(whole - (10 + 15) / 2 * 1e-6)


def test_overlapping_spans_of_one_name_count_once():
    tr = stepped([("serve.retire", 12, 20), ("serve.retire", 15, 18)])
    assert spans.segment_gap_in_ms(tr, "serve.retire") == pytest.approx(
        4e-6)


def test_a_span_straddling_a_segment_counts_only_its_idle_part():
    # [5, 12] holds 2 idle ns after the first segment ends at 10
    tr = stepped([("serve.sync", 5, 12)])
    assert spans.segment_gap_in_ms(tr, "serve.sync") == pytest.approx(1e-6)
    # a span wholly inside a segment reads nothing idle, but reads 0
    tr = stepped([("serve.sync", 2, 8)])
    assert spans.segment_gap_in_ms(tr, "serve.sync") == 0


def test_no_spans_read_none():
    tr = stepped([])
    for name in list(SPAN_METRICS.values()) + ["queue_wait_ms.serve"]:
        assert reader(name)({"trace": tr}) is None
        assert reader(name)({"trace": None}) is None
    # a single segment leaves no interval between two
    tr = stepped(STEPS)
    tr.modules = {D0: tr.modules[D0][:1], D1: tr.modules[D1][:1]}
    assert spans.segment_gap_in_ms(tr, "serve.admit") is None


def test_queue_wait_of_a_known_queue():
    # enqueued by 10, 20, 30, 90; admitted from 15, 40, 70. Q(t) is 1 on
    # [10, 15), [20, 30), [40, 70) and [90, 100]; 2 on [30, 40)
    host = [("serve.enqueue", 8, 10), ("serve.enqueue", 18, 20),
            ("serve.enqueue", 28, 30), ("serve.enqueue", 88, 90),
            ("serve.admit", 15, 17), ("serve.admit", 40, 42),
            ("serve.admit", 70, 75)]
    tr = xplane.Trace((0, 100), {}, {}, host)
    area = 5 + 10 + 2 * 10 + 30 + 10
    got = reader("queue_wait_ms.serve")({"trace": tr})
    assert got == pytest.approx(area / 3 * 1e-6)
    # with no admission there is nothing to divide by
    tr = xplane.Trace((0, 100), {}, {}, host[:4])
    assert spans.queue_wait_ms(tr) is None


def test_intersect():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45)]
    assert spans.intersect(a, b) == [(5, 10), (20, 25), (28, 30), (40, 45)]
    assert spans.intersect(a, []) == []
