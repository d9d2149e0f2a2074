"""The engine's ``engine.step`` spans on a trace recorded on a TPU v5e (a
traced run of ``qwen3-4b.chat``, trimmed to seven ticks: four mixed, three
decode), against the benchmark's own ``bench.tick`` spans. Plain Python: no
JAX, no chip."""

import gzip
import json

import pytest

from bench import harness, trace_reduce as tr, work

TRACE = harness.BENCH / "testdata" / "qwen3-4b.chat.engine.trace.json.gz"
# mean device time of the trace's mixed and decode ticks, in ms
MIXED_MS = 81.97214824999999
DECODE_MS = 51.964281666666665


@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(TRACE, "rt") as f:
        return json.load(f)


def _steps(trace):
    return {e[3]["step_num"]: (e[1], e[1] + e[2], e[3])
            for e in trace["engine"] if e[0] == "engine.step"}


def _rows(stats):
    return [(int(n), int(c), bool(int(s))) for n, c, s in
            (r.split(":") for r in stats["rows"].split(";"))]


def test_one_engine_step_inside_each_bench_tick(chip_trace):
    steps = _steps(chip_trace)
    ticks = {s: (a, b) for s, a, b in tr.ticks(chip_trace["host"])}
    assert len(steps) == 7 and sorted(steps) == sorted(ticks)
    for s, (a, b, _) in steps.items():
        assert ticks[s][0] <= a < b <= ticks[s][1]


def test_mono_ns_is_one_offset(chip_trace):
    offs = [a - st["mono_ns"] for a, _, st in _steps(chip_trace).values()]
    assert max(offs) - min(offs) < 1_000_000


def test_engine_step_device_time_equals_bench_tick(chip_trace):
    """The device time a tick's ops take, grouped by the engine's span or
    by the benchmark's, is the same: a reader can move onto the engine's
    spans without moving its numbers."""
    ops = chip_trace["device_ops"]
    steps = _steps(chip_trace)
    by_engine = tr.device_ns_per_tick(
        ops, [(s, a, b) for s, (a, b, _) in steps.items()])
    assert by_engine == tr.device_ns_per_tick(
        ops, tr.ticks(chip_trace["host"]))
    mixed = [by_engine[s] for s, (_, _, st) in steps.items()
             if st["has_prefill"]]
    decode = [by_engine[s] for s, (_, _, st) in steps.items()
              if not st["has_prefill"]]
    assert (len(mixed), len(decode)) == (4, 3)
    assert sum(mixed) / len(mixed) * 1e-6 == pytest.approx(MIXED_MS)
    assert sum(decode) / len(decode) * 1e-6 == pytest.approx(DECODE_MS)


def test_rows_agree_with_counts_and_feed_work(chip_trace):
    conf = harness.load_cell("qwen3-4b.chat").conf
    for _, _, st in _steps(chip_trace).values():
        rows = _rows(st)
        prefill = [r for r in rows if r[0] > 1]
        assert len(prefill) == st["rows_prefill"]
        assert len(rows) - len(prefill) == st["rows_decode"]
        assert sum(n for n, _, _ in rows) == st["valid_tokens"]
        assert sum(n for n, _, _ in prefill) == st["prefill_tokens"]
        assert sum(s for _, _, s in rows) == st["sampled_rows"]
        assert bool(st["has_prefill"]) == bool(prefill)
        assert st["width"] == (32 if prefill else 1)
        assert st["compiles"] == 0
        assert work.tick_work(conf, rows).flops > 0
