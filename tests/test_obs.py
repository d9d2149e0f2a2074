"""Tracing & telemetry (repro.obs): span nesting, request timelines
under preemption/replay and speculative rollback, Perfetto/JSONL export
validity, registry/exporter parity, summary() empty-window semantics,
the disabled-mode fast path, and greedy token-identity tracing on/off."""

import importlib.util
import json
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ObsConfig, ServeConfig, SpecConfig
from repro.models import Model
from repro.obs import (NULL_TRACER, Registry, Tracer, make_tracer,
                       perfetto_trace, write_jsonl, write_perfetto)
from repro.obs.trace import NULL_SPAN
from repro.serve import metrics as metrics_mod
from repro.serve.engine import Engine
from repro.serve.scheduler import Request

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "check_trace.py")
_spec = importlib.util.spec_from_file_location("check_trace", _TOOLS)
check_trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_trace)


@pytest.fixture(scope="module")
def nectar():
    cfg = get_config("nectar-relu-llama-1.7m")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=int(n), dtype=np.int32)
            for n in lengths]


def _serve(cfg, params, prompts, max_new=8, **scfg_kw):
    eng = Engine(cfg, params, ServeConfig(**scfg_kw))
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    done = eng.run(reqs, max_steps=2000)
    return {i: [int(t) for t in r.tokens_out] for i, r in done.items()}, eng


OBS = ObsConfig(enabled=True)


# ---------------------------------------------------------------------------
# tracer core


def test_span_nesting_and_ordering():
    """Spans record at exit with correct depth; tick_stats attributes
    device_wait to device_ms and the rest to host_ms."""
    tr = Tracer(OBS)
    with tr.tick():
        with tr.span("schedule"):
            with tr.span("admit"):
                pass
        with tr.span("device_wait"):
            time.sleep(0.002)
        tr.tick_attrs(width=4, pad_waste_frac=0.5)
    assert [s.name for s in tr.spans] == ["admit", "schedule",
                                          "device_wait", "tick"]
    by = {s.name: s for s in tr.spans}
    assert by["admit"].depth == 2
    assert by["schedule"].depth == 1
    assert by["tick"].depth == 0
    # containment: child spans lie inside their parents
    assert by["schedule"].t0 <= by["admit"].t0
    assert by["admit"].t1 <= by["schedule"].t1
    assert by["tick"].t0 <= by["schedule"].t0
    [t] = tr.tick_stats
    assert t["tick"] == 0 and t["width"] == 4
    assert t["device_ms"] >= 2.0
    assert t["host_ms"] + t["device_ms"] == pytest.approx(t["dur_ms"])
    assert tr.tick_summary()["pad_waste_frac"] == 0.5


def test_tracer_max_events_bound():
    """Past ObsConfig.max_events new records drop and are COUNTED — a
    truncated trace must be detectable, never silently wrapped."""
    tr = Tracer(ObsConfig(enabled=True, max_events=4))
    for i in range(10):
        tr.event(0, "e", i=i)
    assert len(tr.events) == 4
    assert tr.dropped == 6
    tr.reset()
    assert tr.dropped == 0 and not tr.events


def test_tick_summary_empty_is_none():
    tr = Tracer(OBS)
    s = tr.tick_summary()
    assert s["n_ticks"] == 0
    assert s["host_ms_per_tick"] is None
    assert s["device_ms_per_tick"] is None
    assert s["pad_waste_frac"] is None


# ---------------------------------------------------------------------------
# disabled-mode fast path


def test_null_tracer_shared_singletons():
    """make_tracer(disabled) returns the module singleton; its span() is
    the shared no-op CM — no allocation on the disabled path."""
    assert make_tracer(None) is NULL_TRACER
    assert make_tracer(ObsConfig(enabled=False)) is NULL_TRACER
    assert NULL_TRACER.span("x", a=1) is NULL_SPAN
    assert NULL_TRACER.tick() is NULL_SPAN
    NULL_TRACER.event(0, "arrival")          # no-op, records nothing
    assert NULL_TRACER.events == ()
    assert not NULL_TRACER.enabled


def test_disabled_overhead_under_2pct(nectar):
    """Acceptance: the per-tick cost of the disabled tracer hooks (one
    tick() + the phase span()/event() calls a busy tick makes) is < 2%
    of a real measured tick. The hooks are shared no-op singletons, so
    this holds by construction — the assert pins it against regression."""
    cfg, _, params = nectar
    eng = Engine(cfg, params, ServeConfig(
        paged=True, max_batch=2, max_seq=64, block_size=8,
        prefill_chunk=16))
    assert eng.tracer is NULL_TRACER
    reqs = [Request(rid=i, prompt=p, max_new=4)
            for i, p in enumerate(_prompts(cfg, [8, 8]))]
    eng.run(reqs, max_steps=200)             # warm the jit buckets
    reqs2 = [Request(rid=10 + i, prompt=p, max_new=8)
             for i, p in enumerate(_prompts(cfg, [8, 8], seed=1))]
    t0 = time.perf_counter()
    n_ticks = 0
    pending = list(reqs2)
    while pending or eng._busy():
        while pending and eng.add_request(pending[0]):
            pending.pop(0)
        eng.step()
        n_ticks += 1
    tick_s = (time.perf_counter() - t0) / max(n_ticks, 1)

    # one tick's worth of disabled hooks, many times over
    N = 2000
    tr = NULL_TRACER
    t0 = time.perf_counter()
    for _ in range(N):
        with tr.tick():
            with tr.span("schedule"):
                with tr.span("admit"):
                    tr.event(0, "admitted", slot=0)
            with tr.span("batch_assemble"):
                tr.tick_attrs(width=1, pad_waste_frac=0.0)
            with tr.span("device_dispatch", width=1, has_prefill=False):
                pass
            with tr.span("sample_sync", rows=2):
                pass
            with tr.span("postprocess"):
                tr.event(0, "first_token")
    hook_s = (time.perf_counter() - t0) / N
    assert hook_s < 0.02 * tick_s, (hook_s, tick_s)


def test_greedy_tokens_identical_tracing_on_off(nectar):
    """Acceptance: tracing observes, never schedules — greedy output is
    token-identical with obs on and off (the device fence changes timing
    only)."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 21, 9])
    kw = dict(max_batch=2, max_seq=64, paged=True, block_size=8,
              prefill_chunk=16)
    off, _ = _serve(cfg, params, prompts, **kw)
    on, eng = _serve(cfg, params, prompts, obs=OBS, **kw)
    assert off == on
    assert eng.tracer.n_ticks > 0 and eng.tracer.spans


# ---------------------------------------------------------------------------
# request timelines


def _names(tracer, rid):
    return [e.name for e in tracer.timeline(rid)]


def test_timeline_lifecycle_complete(nectar):
    cfg, _, params = nectar
    _, eng = _serve(cfg, params, _prompts(cfg, [5, 40]), obs=OBS,
                    max_batch=2, max_seq=64, paged=True, block_size=8,
                    prefill_chunk=16)
    for rid in (0, 1):
        names = _names(eng.tracer, rid)
        assert names[0] == "arrival"
        assert names[-1] == "finish"
        assert "admitted" in names and "first_token" in names
        assert names.count("finish") == 1
        assert names.index("admitted") < names.index("first_token")
    # the 40-token prompt needed multiple prefill chunks
    assert _names(eng.tracer, 1).count("prefill_chunk") >= 2


def test_timeline_preemption_and_replay(nectar):
    """A preempted request's timeline shows preempted -> re-admitted ->
    replayed prefill -> replay_done, and still exactly one finish."""
    cfg, _, params = nectar
    _, eng = _serve(cfg, params, _prompts(cfg, [20, 20]), max_new=16,
                    obs=OBS, max_batch=2, max_seq=64, paged=True,
                    block_size=8, prefill_chunk=8, n_kv_blocks=8)
    tr = eng.tracer
    victims = {e.rid for e in tr.events if e.name == "preempted"}
    assert victims, "trace did not provoke a preemption"
    for rid in victims:
        names = _names(tr, rid)
        i = names.index("preempted")
        tail = names[i:]
        assert "admitted" in tail and "replay_done" in tail
        assert tail.index("admitted") < tail.index("replay_done")
        assert names.count("finish") == 1 and names[-1] == "finish"


def test_timeline_spec_verify_and_rollback(nectar):
    """Speculative rows log spec_draft/spec_verify per pass; rejected
    tails log spec_rollback; per-event counts reconcile with the
    registry totals."""
    cfg, _, params = nectar
    _, eng = _serve(cfg, params, _prompts(cfg, [12, 12]), max_new=10,
                    obs=OBS, max_batch=2, max_seq=96, paged=True,
                    block_size=8, prefill_chunk=16,
                    spec=SpecConfig(drafter="ngram", k=3))
    tr = eng.tracer
    verifies = [e for e in tr.events if e.name == "spec_verify"]
    assert verifies
    reg = eng.metrics.registry
    assert sum(e.attrs["drafted"] for e in verifies) \
        == reg.value("spec_drafted_tokens_total")
    assert sum(e.attrs["emitted"] for e in verifies) \
        == reg.value("spec_emitted_tokens_total")
    for e in verifies:
        assert 0 <= e.attrs["accepted"] <= e.attrs["drafted"]
    for e in tr.events:
        if e.name == "spec_rollback":
            assert e.attrs["rejected"] > 0


def test_spec_per_request_reconciles_with_tokens(nectar):
    """Acceptance: per-request realized spec counters reconcile exactly —
    emitted sums match the fleet counter, and each request's emitted
    tokens equal its tokens_out minus the prefill-emitted first token."""
    cfg, _, params = nectar
    toks, eng = _serve(cfg, params, _prompts(cfg, [12, 12, 12]),
                       max_new=10, obs=OBS, max_batch=2, max_seq=96,
                       paged=True, block_size=8, prefill_chunk=16,
                       spec=SpecConfig(drafter="ngram", k=3))
    s = eng.metrics.summary()
    per_req = s["spec_per_request"]
    assert per_req
    reg = eng.metrics.registry
    assert sum(r["emitted"] for r in per_req.values()) \
        == reg.value("spec_emitted_tokens_total")
    assert sum(r["drafted"] for r in per_req.values()) \
        == reg.value("spec_drafted_tokens_total")
    for rid, r in per_req.items():
        # verify passes emit everything after the first (prefill) token
        assert r["emitted"] == len(toks[rid]) - 1
        assert r["acceptance"] is None or 0.0 <= r["acceptance"] <= 1.0
        assert r["tokens_per_verify"] >= 1.0


# ---------------------------------------------------------------------------
# exporters


def test_perfetto_export_valid_and_monotonic(nectar, tmp_path):
    cfg, _, params = nectar
    _, eng = _serve(cfg, params, _prompts(cfg, [5, 30]), obs=OBS,
                    max_batch=2, max_seq=64, paged=True, block_size=8,
                    prefill_chunk=16)
    trace = perfetto_trace(eng.tracer, eng.metrics.registry)
    evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    assert evs
    assert all(b["ts"] >= a["ts"] for a, b in zip(evs, evs[1:]))
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
    # one engine lane per phase name, one request lane per rid
    lanes = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e.get("pid") == 1
             and e["name"] == "thread_name"}
    assert {"tick", "schedule", "device_dispatch", "device_wait",
            "sample_sync", "postprocess"} <= lanes
    assert trace["metadata"]["n_ticks"] == eng.tracer.n_ticks
    assert trace["metadata"]["metrics"]["request_finished_total"] == 2

    p = write_perfetto(eng.tracer, str(tmp_path / "t.trace.json"),
                       registry=eng.metrics.registry)
    assert check_trace.check_perfetto(p) == []
    j = write_jsonl(eng.tracer, str(tmp_path / "t.events.jsonl"))
    assert check_trace.check_jsonl(j) == []
    with open(j) as f:
        kinds = [json.loads(ln)["kind"] for ln in f]
    assert kinds[0] == "meta"
    assert {"span", "event", "tick"} <= set(kinds)


def test_check_trace_catches_corruption(tmp_path):
    bad = tmp_path / "bad.trace.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "ts": 10.0, "dur": 1.0},
        {"ph": "X", "ts": 5.0, "dur": -2.0},
        {"ph": "?", "ts": 6.0},
    ]}))
    errs = check_trace.check_perfetto(str(bad))
    assert any("not monotonic" in e for e in errs)
    assert any("bad dur" in e for e in errs)
    assert any("unknown ph" in e for e in errs)
    badl = tmp_path / "bad.events.jsonl"
    badl.write_text(
        json.dumps({"kind": "event", "rid": 0, "name": "finish",
                    "ts_us": 1.0}) + "\n"
        + json.dumps({"kind": "event", "rid": 0, "name": "arrival",
                      "ts_us": 2.0}) + "\n")
    errs = check_trace.check_jsonl(str(badl))
    assert any("precedes" in e for e in errs)
    assert any("no meta header" in e for e in errs)


# ---------------------------------------------------------------------------
# registry


def test_registry_basics_and_parity():
    reg = Registry()
    c = reg.counter("x_events_total", help="things")
    c.inc()
    c.inc(3)
    assert reg.counter("x_events_total") is c      # get-or-create
    g = reg.gauge("x_depth")
    g.set(7)
    reg.gauge_group("pool", lambda: {"free": 5, "name": "skip-me",
                                     "frag": 0.25})
    h = reg.histogram("x_wait_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(10.0)
    snap = reg.collect()
    assert snap["x_events_total"] == 4
    assert snap["x_depth"] == 7
    assert snap["pool_free"] == 5 and snap["pool_frag"] == 0.25
    assert "pool_name" not in snap                 # non-numeric skipped
    assert snap["x_wait_seconds"]["count"] == 3
    assert snap["x_wait_seconds"]["mean"] == pytest.approx(10.55 / 3)

    text = reg.prometheus_text()
    assert "# TYPE x_events_total counter" in text
    assert "x_events_total 4" in text
    assert "# HELP x_events_total things" in text
    assert 'x_wait_seconds_bucket{le="0.1"} 1' in text
    assert 'x_wait_seconds_bucket{le="+Inf"} 3' in text
    assert "x_wait_seconds_count 3" in text
    assert "pool_free 5" in text

    with pytest.raises(ValueError):
        reg.gauge("x_events_total")                # type mismatch


def test_registry_dead_gauge_group_survives():
    reg = Registry()

    def boom():
        raise RuntimeError("gone")

    reg.gauge_group("dead", boom)
    reg.counter("ok_total").inc()
    assert reg.collect()["ok_total"] == 1          # scrape survives
    assert "dead" not in reg.prometheus_text()


def test_prometheus_label_value_escaping():
    """Exposition escaping: label values from the wild (request ids
    with quotes, backslashes, newlines) must round-trip per the
    Prometheus text format — backslash escaped FIRST, then quote, then
    newline — and HELP lines escape backslash/newline."""
    from repro.obs.registry import escape_help, escape_label_value
    assert escape_label_value('plain') == 'plain'
    assert escape_label_value('sa"id') == 'sa\\"id'
    assert escape_label_value('a\\b') == 'a\\\\b'
    assert escape_label_value('two\nlines') == 'two\\nlines'
    # order matters: the backslash introduced by quote-escaping must
    # not itself get re-escaped
    assert escape_label_value('\\"') == '\\\\\\"'
    assert escape_help('why\\so\nserious "ok"') == \
        'why\\\\so\\nserious "ok"'

    reg = Registry()
    evil = 'req\\7 say "hi"\nplease'
    reg.labeled_gauge_group("bucket_attainment", "bucket",
                            lambda: {evil: {"attainment": 0.5}})
    text = reg.prometheus_text()
    want = ('bucket_attainment_attainment{bucket='
            '"req\\\\7 say \\"hi\\"\\nplease"} 0.5')
    assert want in text
    assert "\nreq" not in text                     # no raw newline leaked


def test_prometheus_labeled_gauges_help_type_and_repull():
    """Labeled gauge groups: one TYPE line per metric name (not per
    series), every plain metric keeps HELP/TYPE, and the group callable
    is re-evaluated at EVERY scrape — a Prometheus poll sees current
    values, not registration-time ones."""
    reg = Registry()
    reg.counter("x_total", help="with help").inc()
    pulls = {"n": 0}

    def fn():
        pulls["n"] += 1
        return {"decode": {"attain": pulls["n"]},
                "prefill16": {"attain": pulls["n"] * 10}}

    reg.labeled_gauge_group("bucket", "bucket", fn)
    t1 = reg.prometheus_text()
    assert "# HELP x_total with help" in t1
    assert "# TYPE x_total counter" in t1
    assert t1.count("# TYPE bucket_attain gauge") == 1
    assert 'bucket_attain{bucket="decode"} 1' in t1
    assert 'bucket_attain{bucket="prefill16"} 10' in t1
    t2 = reg.prometheus_text()                     # second scrape
    assert 'bucket_attain{bucket="decode"} 2' in t2
    assert pulls["n"] == 2
    # collect() parity: the labeled series land in the snapshot too
    snap = reg.collect()
    assert snap['bucket_attain{bucket="decode"}'] == 3


def test_engine_registry_matches_summary(nectar):
    """Exporter parity: summary(), registry.collect(), and the
    Prometheus text all read the same numbers."""
    cfg, _, params = nectar
    _, eng = _serve(cfg, params, _prompts(cfg, [5, 30]), obs=OBS,
                    max_batch=2, max_seq=64, paged=True, block_size=8,
                    prefill_chunk=16, prefix_cache=True)
    s = eng.metrics.summary()
    reg = eng.metrics.registry
    snap = reg.collect()
    assert snap["engine_decode_steps_total"] == s["decode_steps"]
    assert snap["engine_prefill_chunks_total"] == s["prefill_chunks"]
    assert snap["sched_preemptions_total"] == s["evictions"]
    assert snap["request_finished_total"] == s["n_finished"]
    assert snap["prefix_lookups_total"] == s["prefix_lookups"]
    assert snap["traffic_weight_bytes_total"] == s["weight_bytes"]
    # pull-style gauge groups mirror the live stats dicts
    pool = eng.pool.stats()
    for k, v in pool.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        assert snap[f"pool_{k}"] == v, k
    text = reg.prometheus_text()
    assert f"request_finished_total {s['n_finished']}" in text
    assert f"engine_decode_steps_total {s['decode_steps']}" in text


# ---------------------------------------------------------------------------
# summary() empty-window semantics (satellite: zero-request edge cases)


def test_summary_zero_requests_is_null_not_zero(nectar):
    cfg, _, params = nectar
    col = metrics_mod.MetricsCollector(cfg, ServeConfig(paged=True))
    s = col.summary()
    assert s["n_finished"] == 0
    assert s["tokens_per_s"] is None
    assert s["ttft_p50_ms"] is None
    assert s["ttft_p99_ms"] is None
    assert s["latency_p50_ms"] is None
    assert s["tpot_p50_ms"] is None
    assert s["ttft_hit_p50_ms"] is None
    # ratio guards intentionally stay 0.0 (benchmarks format them)
    assert s["spec_acceptance_rate"] == 0.0
    assert s["prefix_hit_rate"] == 0.0


def test_summary_unfinished_requests_are_null(nectar):
    """Arrivals with no finishes (the all-preempted / still-running
    window): percentiles and throughput must be None, and the arrival
    is still counted."""
    cfg, _, params = nectar
    col = metrics_mod.MetricsCollector(cfg, ServeConfig(paged=True))
    col.on_arrival(0, 10)
    col.on_preemption(0)
    s = col.summary()
    assert s["n_finished"] == 0 and s["evictions"] == 1
    assert s["tokens_per_s"] is None
    assert s["ttft_p50_ms"] is None and s["latency_p99_ms"] is None
    assert col.registry.value("request_arrivals_total") == 1
    assert col.registry.value("request_finished_total") == 0


# ---------------------------------------------------------------------------
# async engine attribution (docs/async.md): deferred reconciliation


def _serve_async(cfg, params, prompts, max_device_ticks, max_new=10,
                 sync_every=0):
    from repro.configs.base import AsyncConfig
    return _serve(cfg, params, prompts, max_new=max_new, obs=OBS,
                  max_batch=2, max_seq=64, paged=True, block_size=8,
                  prefill_chunk=16,
                  async_cfg=AsyncConfig(enabled=True,
                                        max_device_ticks=max_device_ticks,
                                        sync_every=sync_every))


def test_async_overlap_spans_attribute_deferred_reconcile(nectar, tmp_path):
    """Overlap ticks (max_device_ticks=1) defer the host sync one tick:
    the sample_sync span that blocks carries ``reconciles_tick`` naming
    the DISPATCH tick, the per-tick host/device attribution identity
    still holds, and the exported JSONL passes --expect-ordering."""
    cfg, _, params = nectar
    _, eng = _serve_async(cfg, params, _prompts(cfg, [5, 9]),
                          max_device_ticks=1)
    tr = eng.tracer
    assert eng.async_stats()["overlap_ticks"] > 0
    deferred = [s for s in tr.spans if s.name == "sample_sync"
                and "reconciles_tick" in s.attrs
                and s.attrs["reconciles_tick"] < s.tick]
    assert deferred, "no overlap tick deferred its reconcile"
    for s in deferred:
        assert s.attrs["reconciles_tick"] == s.tick - 1
    # attribution identity survives deferral: device_wait lands in
    # device_ms, everything else in host_ms, per tick entry
    for t in tr.tick_stats:
        assert t["host_ms"] + t["device_ms"] \
            == pytest.approx(t["dur_ms"])
    overlap = [t for t in tr.tick_stats
               if t.get("async_mode") == "overlap"]
    assert overlap and all(t["device_ticks"] == 1 for t in overlap)
    j = write_jsonl(tr, str(tmp_path / "a.events.jsonl"))
    assert check_trace.check_jsonl(j, expect_ordering=True) == []


def test_async_loop_burst_device_tick_normalization(nectar, tmp_path):
    """A K-tick device burst records ONE tick_stats entry with
    device_ticks=K; tick_summary normalizes per-device-tick so
    host_ms_per_tick stays comparable to the synchronous engine, and
    the engine's device_ticks property reconciles runner steps with
    burst iterations."""
    cfg, _, params = nectar
    _, eng = _serve_async(cfg, params, _prompts(cfg, [5, 9]),
                          max_device_ticks=6)
    tr = eng.tracer
    st = eng.async_stats()
    assert st["loop_bursts"] > 0 and st["loop_device_ticks"] > 0
    bursts = [t for t in tr.tick_stats if t.get("async_mode") == "loop"]
    assert bursts and any(t["device_ticks"] > 1 for t in bursts)
    assert sum(t["device_ticks"] for t in bursts) \
        == st["loop_device_ticks"]
    s = tr.tick_summary()
    assert s["n_device_ticks"] == sum(
        t.get("device_ticks", 1) for t in tr.tick_stats)
    assert s["n_device_ticks"] > s["n_ticks"]
    # normalization: summing host_ms over entries / device ticks
    assert s["host_ms_per_tick"] == pytest.approx(
        sum(t["host_ms"] for t in tr.tick_stats) / s["n_device_ticks"])
    assert eng.device_ticks == eng.runner.n_steps \
        + st["loop_device_ticks"]
    assert 0.0 < st["overlap_frac"] <= 1.0
    j = write_jsonl(tr, str(tmp_path / "l.events.jsonl"))
    assert check_trace.check_jsonl(j, expect_ordering=True) == []
    p = write_perfetto(tr, str(tmp_path / "l.trace.json"))
    assert check_trace.check_perfetto(p) == []


def test_expect_ordering_catches_early_reconcile(tmp_path):
    """The --expect-ordering gate fails when a sample_sync span claims
    to reconcile a tick whose dispatch had not closed yet, and when a
    trace has no sample_sync spans at all."""
    badl = tmp_path / "bad.events.jsonl"
    badl.write_text(
        json.dumps({"kind": "meta", "dropped": 0}) + "\n"
        + json.dumps({"kind": "span", "name": "device_dispatch",
                      "ts_us": 100.0, "dur_us": 50.0, "depth": 1,
                      "tick": 3}) + "\n"
        + json.dumps({"kind": "span", "name": "sample_sync",
                      "ts_us": 120.0, "dur_us": 5.0, "depth": 1,
                      "tick": 4,
                      "attrs": {"reconciles_tick": 3}}) + "\n")
    errs = check_trace.check_jsonl(str(badl), expect_ordering=True)
    assert any("before that tick's device_dispatch closed" in e
               for e in errs)
    # ordering is opt-in: the same file passes without the flag
    assert check_trace.check_jsonl(str(badl)) == []
    empty = tmp_path / "empty.events.jsonl"
    empty.write_text(json.dumps({"kind": "meta", "dropped": 0}) + "\n")
    errs = check_trace.check_jsonl(str(empty), expect_ordering=True)
    assert any("no sample_sync" in e for e in errs)


def test_legacy_engine_timeline_and_summary(nectar):
    """The legacy slot path traces too (arrival/first_token/finish plus
    tick spans) — the obs subsystem is not paged-only."""
    cfg, _, params = nectar
    _, eng = _serve(cfg, params, _prompts(cfg, [5, 9]), obs=OBS,
                    max_batch=2, max_seq=64, paged=False)
    tr = eng.tracer
    for rid in (0, 1):
        names = _names(tr, rid)
        assert names[0] == "arrival" and names[-1] == "finish"
        assert "first_token" in names
    assert {"tick", "device_dispatch", "sample_sync"} \
        <= {s.name for s in tr.spans}
    assert eng.metrics.summary()["ticks"]["n_ticks"] == tr.n_ticks


# ---------------------------------------------------------------------------
# the engine's own tick record: engine.step spans, the one clock, named
# step executables, compile counter, admission stamps, tick gauges


def _profiled_serve(tmp_path, cfg, params, prompts, max_new=6, **scfg_kw):
    """Serve ``prompts`` under the CPU profiler with annotations on;
    returns the engine, the batches its runner ran (n_valid, row_start,
    phase) and the xplane's host events by name."""
    from jax._src.profiler import ProfileData
    obs = ObsConfig(enabled=True, jax_annotations=True)
    eng = Engine(cfg, params, ServeConfig(obs=obs, **scfg_kw))
    batches = []
    orig = eng.runner.step

    def recording(batch, *a, **k):
        batches.append((batch.n_valid.copy(), batch.row_start.copy(),
                        batch.phase.copy()))
        return orig(batch, *a, **k)

    eng.runner.step = recording
    reqs = [Request(rid=eng.new_rid(), prompt=p, max_new=max_new)
            for p in prompts]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs, max_steps=500)
    finally:
        jax.profiler.stop_trace()
    [path] = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
              for f in fs if f.endswith(".xplane.pb")]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (int(e.start_ns), dict(e.stats)))
    return eng, batches, events


def _parse_rows(s):
    return [tuple(int(x) for x in r.split(":")) for r in s.split(";")]


@pytest.fixture(scope="module")
def profiled(nectar, tmp_path_factory):
    cfg, _, params = nectar
    return _profiled_serve(
        tmp_path_factory.mktemp("xplane"), cfg, params,
        _prompts(cfg, [5, 40, 9]), paged=True, max_batch=2, max_seq=64,
        block_size=8, prefill_chunk=16)


def test_engine_step_spans_carry_tick_and_rows(profiled):
    """Every tick runs inside one ``engine.step`` annotation whose stats
    name the tick (step_num), the step shape (width, gen_width,
    has_prefill) and the rows as n_tokens:ctx_before:sampled — the rows
    of the batch the runner executed."""
    from repro.serve.runner import DECODE, IDLE, PREFILL
    eng, batches, ev = profiled
    steps = sorted(ev["engine.step"], key=lambda x: x[1]["step_num"])
    assert [st["step_num"] for _, st in steps] \
        == list(range(eng.tracer.n_ticks))
    ran = [st for _, st in steps if "width" in st]
    assert len(ran) == len(batches) > 0
    assert any(st["has_prefill"] for st in ran)
    assert not all(st["has_prefill"] for st in ran)
    for st, (n_valid, start, phase) in zip(ran, batches):
        live = phase != IDLE
        rows = _parse_rows(st["rows"])
        assert sorted((n, c) for n, c, _ in rows) == sorted(
            zip(n_valid[live].tolist(), start[live].tolist()))
        assert bool(st["has_prefill"]) == bool((phase == PREFILL).any())
        assert st["gen_width"] in eng.runner.widths
        assert st["width"] == (eng.scfg.prefill_chunk
                               if (phase == PREFILL).any()
                               else st["gen_width"])
        assert st["prefill_tokens"] == int(n_valid[phase == PREFILL].sum())
        assert st["sampled_rows"] == sum(s for _, _, s in rows)
        # every decode row samples; only a prompt's last chunk does
        assert int((phase == DECODE).sum()) <= st["sampled_rows"]
        assert "compiles" in st
    # the tracer's own tick stats hold the same rows
    by_tick = {t["tick"]: t for t in eng.tracer.tick_stats}
    for _, st in steps:
        if "rows" in st:
            assert by_tick[st["step_num"]]["rows"] == st["rows"]


def test_engine_step_mono_ns_maps_to_profiler_clock(profiled):
    """``start_ns - mono_ns`` is one offset for every tick of a trace:
    the engine's monotonic stamps land on the profiler timeline."""
    _, _, ev = profiled
    offs = [start - st["mono_ns"] for start, st in ev["engine.step"]]
    assert len(offs) > 3
    assert max(offs) - min(offs) < 1_000_000


def test_device_dispatch_span_carries_attributes(profiled):
    """Span attributes reach the profiler: device_dispatch names its
    width and prefill presence, sample_sync its rows."""
    eng, _, ev = profiled
    disp = ev["device_dispatch"]
    assert disp and all({"width", "has_prefill"} <= set(st)
                        for _, st in disp)
    assert {st["width"] for _, st in disp} \
        <= {*eng.runner.widths, eng.scfg.prefill_chunk}
    assert all("rows" in st for _, st in ev["sample_sync"])


def test_compile_of_new_bucket_is_counted_and_named(nectar):
    """A step bucket compiled inside a tick raises engine_compiles_total
    under the bucket's name, and the tick that compiled it says so."""
    cfg, _, params = nectar
    # prefill_chunk 13: a bucket no other test compiles
    _, eng = _serve(cfg, params, _prompts(cfg, [20]), max_new=3,
                    obs=OBS, paged=True, max_batch=2, max_seq=64,
                    block_size=8, prefill_chunk=13)
    reg = eng.metrics.registry
    snap = reg.collect()
    assert reg.value("engine_compiles_total") >= 1
    # the first tick with a prefill row builds the grouped step of every
    # prefill bucket (max_batch 2: one and two rows of 13)
    for p in (1, 2):
        assert snap['engine_compiles_total{fun_name="jit(serve_step_w1_'
                    f'p{p}x13)"}}'] == 1
    names = set(eng.metrics.compiles.values)
    assert any(n.startswith("jit(serve_step_w") for n in names)
    first = eng.tracer.tick_stats[0]
    assert first["compiles"] >= 1 and first["width"] == 13
    assert sum(t["compiles"] for t in eng.tracer.tick_stats) \
        == reg.value("engine_compiles_total")
    text = reg.prometheus_text()
    assert "# TYPE engine_compiles_total counter" in text
    assert 'engine_compiles_total{fun_name="jit(serve_step_w1_' \
        'p1x13)"} 1' in text
    # a fresh window starts the count again; warm buckets do not compile
    eng.reset_metrics()
    eng.run([Request(rid=eng.new_rid(), prompt=_prompts(cfg, [20])[0],
                     max_new=3)], max_steps=100)
    assert eng.metrics.registry.value("engine_compiles_total") == 0


def test_grouped_buckets_build_at_first_prefill_tick(nectar):
    """The first tick with a prefill row builds the grouped step of every
    prefill bucket: later ticks with two, three and four prefill rows
    compile nothing."""
    cfg, _, params = nectar
    # prefill_chunk 12: a bucket no other test compiles
    _, eng = _serve(cfg, params, _prompts(cfg, [20]), max_new=3, obs=OBS,
                    paged=True, max_batch=4, max_seq=96, block_size=8,
                    prefill_chunk=12)
    assert eng.runner.prefill_buckets == [1, 4]
    assert {"jit(serve_step_w1_p1x12)",
            "jit(serve_step_w1_p4x12)"} \
        <= set(eng.metrics.compiles.values)
    eng.reset_metrics()
    # one to four chunks each: ticks with 4, 3, 2 and 1 prefill rows
    eng.run([Request(rid=eng.new_rid(), prompt=p, max_new=3)
             for p in _prompts(cfg, [12, 24, 36, 48], seed=3)],
            max_steps=200)
    reg = eng.metrics.registry
    assert {2, 3, 4} <= {t["rows_prefill"] for t in eng.tracer.tick_stats}
    assert reg.value("engine_compiles_total") == 0
    mixed = reg.labeled_counter("engine_mixed_ticks_total",
                                "prefill_bucket").values
    assert set(mixed) == {"1", "4"}


def test_grouped_ticks_carry_prefill_group(profiled):
    """Grouped ticks name their prefill group on ``engine.step``
    (prefill_bucket P, prefill_width C), charge pad waste against the
    shape that ran (B + P*C token slots), and are counted by P in
    engine_mixed_ticks_total."""
    from repro.serve.runner import PREFILL
    eng, batches, ev = profiled
    B, C = eng.scfg.max_batch, eng.scfg.prefill_chunk
    steps = sorted((st for _, st in ev["engine.step"] if "width" in st),
                   key=lambda st: st["step_num"])
    by_tick = {t["tick"]: t for t in eng.tracer.tick_stats}
    counted = {}
    for st, (n_valid, _, phase) in zip(steps, batches):
        t = by_tick[st["step_num"]]
        n_pf = int((phase == PREFILL).sum())
        if not n_pf:
            assert "prefill_bucket" not in st
            assert t["pad_waste_frac"] == pytest.approx(
                1 - n_valid.sum() / (B * st["width"]))
            continue
        P = eng.runner.prefill_bucket_for(n_pf)
        assert st["prefill_bucket"] == t["prefill_bucket"] == P
        assert st["prefill_width"] == t["prefill_width"] == C
        assert t["pad_waste_frac"] == pytest.approx(
            1 - n_valid.sum() / (B + P * C))
        counted[str(P)] = counted.get(str(P), 0) + 1
    assert counted
    assert eng.metrics.registry.labeled_counter(
        "engine_mixed_ticks_total", "prefill_bucket").values == counted
    text = eng.metrics.registry.prometheus_text()
    assert "# TYPE engine_mixed_ticks_total counter" in text
    assert 'engine_mixed_ticks_total{prefill_bucket="1"}' in text


def test_queue_wait_is_admission_minus_arrival(nectar):
    """Every request is stamped when the scheduler first admits it;
    queue_wait_samples are admitted_at - arrival, one per request, and
    the histogram counts them."""
    cfg, _, params = nectar
    # one slot: the second and third requests wait for the first
    _, eng = _serve(cfg, params, _prompts(cfg, [5, 9, 7]), max_new=4,
                    paged=True, max_batch=1, max_seq=64, block_size=8,
                    prefill_chunk=16)
    recs = sorted(eng.metrics.requests.values(), key=lambda r: r.admitted_at)
    s = eng.metrics.summary()
    assert s["queue_wait_samples"] == [r.admitted_at - r.arrival
                                       for r in recs]
    assert all(r.arrival <= r.admitted_at <= r.first_token_at for r in recs)
    assert recs[-1].queue_wait > recs[0].queue_wait
    h = eng.metrics.registry.collect()["request_queue_wait_seconds"]
    assert h["count"] == 3


def test_tick_gauges_and_slowest_tick(nectar):
    """engine_tick_seconds counts every Engine.step, the max gauge is the
    longest, and under tracing slowest_tick is that tick's stats entry
    with its phases and compiles."""
    cfg, _, params = nectar
    _, eng = _serve(cfg, params, _prompts(cfg, [5, 30]), obs=OBS,
                    paged=True, max_batch=2, max_seq=64, block_size=8,
                    prefill_chunk=16)
    reg = eng.metrics.registry
    h = reg.collect()["engine_tick_seconds"]
    assert h["count"] == eng.tracer.n_ticks
    tmax = reg.value("engine_tick_max_seconds")
    assert tmax * 1e3 >= max(t["dur_ms"] for t in eng.tracer.tick_stats)
    slow = eng.tracer.slowest_tick
    assert slow["dur_ms"] == max(t["dur_ms"] for t in eng.tracer.tick_stats)
    assert slow["phases_ms"] and "compiles" in slow
    assert eng.metrics.summary()["slowest_tick"] is slow
    # the tracer's clock is the collector's: tick starts sit between the
    # first arrival and the last finish
    recs = eng.metrics.requests.values()
    lo = min(r.arrival for r in recs)
    hi = max(r.finished_at for r in recs)
    assert all(lo - 1.0 <= t["t0_mono_s"] <= hi for t in eng.tracer.tick_stats)
    eng.reset_metrics()
    assert eng.metrics.registry.value("engine_tick_max_seconds") == 0
    assert eng.tracer.slowest_tick is None


def test_tick_record_without_annotations(nectar, monkeypatch):
    """With the tracer disabled, or recording without profiler
    annotations, no annotation is built; the tick histogram, max gauge
    and admission stamps fill all the same."""
    cfg, _, params = nectar
    built = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: built.append(a))
    for obs in (ObsConfig(), ObsConfig(enabled=True)):
        _, eng = _serve(cfg, params, _prompts(cfg, [5, 9]), max_new=4,
                        obs=obs, paged=True, max_batch=2, max_seq=64,
                        block_size=8, prefill_chunk=16)
        reg = eng.metrics.registry
        assert reg.collect()["engine_tick_seconds"]["count"] > 0
        assert reg.value("engine_tick_max_seconds") > 0
        assert len(eng.metrics.summary()["queue_wait_samples"]) == 2
    assert built == []


def test_labeled_counter_registry_and_prometheus():
    reg = Registry()
    c = reg.labeled_counter("engine_compiles_total", "fun_name", "compiles")
    assert reg.labeled_counter("engine_compiles_total", "fun_name") is c
    c.inc('jit(a"b)')
    c.inc("jit(c)", 2)
    assert reg.value("engine_compiles_total") == 3
    snap = reg.collect()
    assert snap["engine_compiles_total"] == 3
    assert snap['engine_compiles_total{fun_name="jit(c)"}'] == 2
    text = reg.prometheus_text()
    assert "# TYPE engine_compiles_total counter" in text
    assert 'engine_compiles_total{fun_name="jit(a\\"b)"} 1' in text
