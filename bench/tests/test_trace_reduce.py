"""The trace reduction, on a small trace recorded on a TPU v5e (a traced
run of ``qwen3-4b.chat``, trimmed to a few ticks) and on hand-made
intervals. Plain Python: no JAX, no chip."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).resolve().parent.parent / "testdata" / \
    "qwen3-4b.chat.trace.json.gz"


@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(TRACE, "rt") as f:
        return json.load(f)


def test_merge_and_covered():
    m = tr.merge([(5, 9), (0, 3), (2, 4), (9, 10), (20, 25)])
    assert m == [(0, 4), (5, 10), (20, 25)]
    assert tr.covered(m, 0, 30) == 14
    assert tr.covered(m, 3, 22) == 1 + 5 + 2
    assert tr.covered(m, 10, 20) == 0


def test_ticks_group_ops_by_host_span():
    host = [["bench.tick", 0, 100, 0], ["bench.tick", 100, 100, 1],
            ["schedule", 0, 10, None]]
    ops = [["a", 10, 20, ], ["b", 25, 10], ["c", 120, 50], ["d", 150, 40]]
    spans = tr.ticks(host)
    assert spans == [(0, 0, 100), (1, 100, 200)]
    assert tr.device_ns_per_tick(ops, spans) == {0: 25, 1: 70}


def test_idle_gaps_named_by_innermost_span():
    host = [["bench.tick", 0, 100, 0], ["sample_sync", 40, 30, None],
            ["bench.tick", 100, 100, 1]]
    merged = tr.merge([(0, 30), (80, 150)])
    gaps = tr.idle_gaps(merged, host, 0, 250, k=3)
    assert gaps[0][0] == "untraced host" and gaps[0][1] == pytest.approx(
        100e-9)
    assert gaps[1] == ["sample_sync", pytest.approx(50e-9)]


def test_top_ops_sum_by_name():
    ops = [["x", 0, 5], ["y", 5, 3], ["x", 8, 4]]
    assert tr.top_ops(ops, 1) == [["x", pytest.approx(9e-9)]]


def test_chip_trace_has_device_ops_and_ticks(chip_trace):
    assert len(chip_trace["device_ops"]) > 100
    spans = tr.ticks(chip_trace["host"])
    assert len(spans) >= 3
    assert all(a < b for _, a, b in spans)


def test_chip_trace_busy_union_and_idle(chip_trace):
    ops = chip_trace["device_ops"]
    spans = tr.ticks(chip_trace["host"])
    lo, hi = spans[0][1], spans[-1][2]
    merged = tr.merge(tr.op_intervals(ops))
    busy = tr.covered(merged, lo, hi)
    total = sum(d for _, s, d in ops if lo <= s and s + d <= hi)
    assert 0 < busy <= hi - lo
    assert busy <= total          # union never exceeds the sum
    per_tick = tr.device_ns_per_tick(ops, spans)
    assert all(0 < per_tick[s] <= b - a for s, a, b in spans)
    assert sum(per_tick.values()) <= busy + 1


def test_chip_trace_top_ops_and_gaps(chip_trace):
    ops = chip_trace["device_ops"]
    top = tr.top_ops(ops)
    assert len(top) == 10
    assert all(top[i][1] >= top[i + 1][1] for i in range(9))
    spans = tr.ticks(chip_trace["host"])
    merged = tr.merge(tr.op_intervals(ops))
    gaps = tr.idle_gaps(merged, chip_trace["host"], spans[0][1],
                        spans[-1][2])
    assert gaps and all(g[1] > 0 for g in gaps)
    assert all(gaps[i][1] >= gaps[i + 1][1] for i in range(len(gaps) - 1))
