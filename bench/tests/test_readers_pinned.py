"""Every metric reader, on the trace recorded on a TPU v5e
(``bench/testdata/qwen3-4b.chat.trace.json.gz``) joined with a fixed
window of ticks, requests and counters, returns the value it returned
when the engine began to annotate its own ticks: the engine's new spans
and counters leave what the benchmark reads unchanged. Plain Python: no
JAX, no chip."""

import gzip
import json
from types import SimpleNamespace

import pytest

from bench import harness, readings
from bench.arrivals import Offered
from bench.peaks import PEAKS

TRACE = harness.BENCH / "testdata" / "qwen3-4b.chat.trace.json.gz"

DECODE = [(1, 400 + 7 * i, True) for i in range(15)]
ROWS = {368: DECODE + [(1, 390, True)],
        369: [(32, 64, False)] + DECODE,
        370: DECODE,
        371: [(20, 96, True)] + DECODE,
        372: DECODE + [(1, 117, True)],
        373: DECODE + [(1, 118, True)]}

# reader -> its value on this run, pinned
PINNED = {
    "ttft_p90_ms": 2280.0000000000005,
    "itl_mean_ms": 71.42857142857143,
    "itl_p95_ms": 89.99999999999986,
    "setup_s": 14.5,
    "itl_prefill_overlap_frac.chat": 25.0,
    "preempt_per_req.chat": 0.25,
    "decode_step_ms.chat": 52.12804775,
    "decode_roofline.chat": 21.229340048907776,
    "step_mfu.chat": 1.7296181187462252,
    "idle_frac.chat": 7.6336757120919145,
}


def _run():
    with gzip.open(TRACE, "rt") as f:
        trace = json.load(f)
    ticks = [harness.Tick(step, 0.0, 0.0, rows,
                          sum(1 for r in rows if r[0] > 1), 0)
             for step, rows in ROWS.items()]
    req = SimpleNamespace(done=True)
    tracks = {
        0: harness.Track(None, req, 1.0, [1.5, 1.56, 1.62, 1.71]),
        1: harness.Track(None, req, 2.0, [4.4, 4.46, 4.55, 4.61, 9.9]),
        2: harness.Track(None, req, 3.0, [3.9, 3.98]),
    }
    refused = [Offered(3, 8.0, None, 4)]
    win = harness.Window(seconds=9.0, t0=0.0, t_end=9.0, t_stop=10.0,
                         tracks=tracks, ticks=ticks, refused=refused,
                         counters_end={"prefill_tokens": 52,
                                       "generated_tokens": 11,
                                       "preemptions": 1,
                                       "gaps_prefill_overlap": 2,
                                       "gaps_steady": 6},
                         compiles_in_window=0)
    cell = harness.load_cell("qwen3-4b.chat")
    return readings.Run(cell=cell, win=win, setup_s=14.5,
                        peak=PEAKS["TPU v5 lite"], trace=trace)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_reader_reads_pinned_value(name):
    got = harness.metric_reader(name).read(_run())
    assert got == pytest.approx(PINNED[name], rel=1e-12, abs=0)
