"""A whole run on the CPU at smoke size with the chip check skipped: the
sound program comes out correct, the float8 control reads above the
limit, and each fault a serving cell can have comes out not correct."""

import json

import jax
import pytest

from bench import harness, run
from bench.tests import smoke

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def _cell():
    spec = {k: [dict(m, workloads=["smoke.chat"]) for m in SPEC[k]]
            for k in ("end_to_end", "per_layer")}
    return smoke.cell(spec=spec)


def _execute(seed, traced=False):
    return run.execute(_cell(), seed, 2.0, traced, jax.devices(), PEAK)


def test_sound_run_is_correct():
    out = _execute(2 ** 31 + 3)
    assert out["correct"] is True
    for k, limit in smoke.SETTINGS["check"].items():
        assert out["check"][k]["value"] <= limit
    assert list(out)[-1] == "check"
    assert {"ttft_p90_ms", "itl_mean_ms", "itl_p95_ms",
            "setup_s"} <= set(out["metrics"])
    assert out["attempted"] > 0 and out["failed"] == 0


def test_traced_run_reads_per_layer_metrics():
    out = _execute(4, traced=True)
    assert out["correct"] is True
    assert "preempt_per_req.chat" in out["metrics"]
    assert "window_s" in out["device"] and "breakdown" in out


def test_control_reads_above_limit():
    cell = _cell()
    params = harness.make_weights(cell, 5)
    eng = harness.make_engine(cell, params, traced=False)
    harness.warm_up(eng, 5)
    from bench import arrivals
    offered = arrivals.generate(cell.mix, cell.settings["rate_per_s"], 2.0,
                                5, cell.conf["vocab_size"])
    win = harness.serve_window(eng, offered, 2.0, harness.CompileCounter())
    harness.free(eng)
    sample = harness.sample_for_check(win, 5)
    prog = harness.check(cell, params, sample)
    ctl = harness.check(cell, params, sample, control=True)
    limit = smoke.SETTINGS["check"]["max_logprob_err"]
    assert prog["max_logprob_err"] <= limit < ctl["max_logprob_err"]


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve import sampling
    orig = sampling.Sampler.__call__

    def altered(self, *a, **k):
        tok, lp = orig(self, *a, **k)
        return (tok + 1) % smoke.CONF["vocab_size"], lp

    monkeypatch.setattr(sampling.Sampler, "__call__", altered)
    assert _execute(6)["correct"] is False


def test_wrong_token_with_its_own_logprob_is_not_correct(monkeypatch):
    from bench import control
    from repro.serve import sampling
    monkeypatch.setattr(sampling.Sampler, "__call__", control.second_best)
    out = _execute(8)
    assert out["correct"] is False
    assert out["check"]["max_logprob_err"]["value"] <= \
        smoke.SETTINGS["check"]["max_logprob_err"]
    assert out["check"]["max_logit_gap"]["value"] > \
        smoke.SETTINGS["check"]["max_logit_gap"]


def test_step_that_keeps_its_state_is_not_correct(monkeypatch):
    from repro.serve import runner
    orig = runner.ModelRunner.step

    def stale(self, batch, *a, **k):
        cache = self.cache
        out = orig(self, batch, *a, **k)
        self.cache = cache
        return out

    monkeypatch.setattr(runner.ModelRunner, "step", stale)
    assert _execute(7)["correct"] is False


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["end_to_end"]
                                  + SPEC["per_layer"]])
def test_every_metric_has_a_reader(name):
    assert callable(harness.metric_reader(name).read)
