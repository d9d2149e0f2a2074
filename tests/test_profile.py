"""Roofline attainment profiling (repro.obs.profile / costmodel) and
the perf-regression sentinel (tools/bench_compare.py, benchmarks.run
history/baselines): per-bucket attainment in (0, 1], scope split vs
bucket totals, profiling-off token identity, surfaces (summary /
Prometheus / Perfetto counters), and the gate's exit behavior on the
committed index vs a synthetic 20% tokens/s regression."""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ObsConfig, ServeConfig
from repro.models import Model
from repro.obs import write_perfetto
from repro.serve.engine import Engine
from repro.serve.metrics import percentile
from repro.serve.scheduler import Request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check_trace = _load("check_trace", "tools/check_trace.py")
bench_compare = _load("bench_compare", "tools/bench_compare.py")


@pytest.fixture(scope="module")
def nectar():
    cfg = get_config("nectar-relu-llama-1.7m")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=int(n), dtype=np.int32)
            for n in lengths]


def _serve(cfg, params, prompts, max_new=8, **scfg_kw):
    eng = Engine(cfg, params, ServeConfig(
        max_batch=2, max_seq=64, paged=True, block_size=8,
        prefill_chunk=16, **scfg_kw))
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    done = eng.run(reqs, max_steps=2000)
    return {i: [int(t) for t in r.tokens_out] for i, r in done.items()}, eng


@pytest.fixture(scope="module")
def profiled(nectar):
    """One profiled serve run shared by the read-only assertions (the
    unrolled-twin compiles in the cost model are the slow part)."""
    cfg, params = nectar
    tokens, eng = _serve(cfg, params, _prompts(cfg, [5, 21, 9]),
                         obs=ObsConfig(enabled=True, profile=True))
    return tokens, eng


# ---------------------------------------------------------------------------
# attainment rows


def test_buckets_attainment_in_unit_interval(profiled):
    """Acceptance: every compiled width bucket reports achieved
    GFLOP/s, GB/s, and attainment in (0, 1] vs the active chip."""
    _, eng = profiled
    rows = eng.profiler.report(eng.tracer.tick_stats)
    assert {r["bucket"] for r in rows} == {"decode", "mixed_p1",
                                           "mixed_p2"}
    for r in rows:
        assert r["width"] == 1
        assert r["bucket"] == (f"mixed_p{r['prefill_bucket']}"
                               if r["prefill_bucket"] else "decode")
        assert r["ticks"] > 0 and r["dev_ms"] > 0
        assert r["GFLOP/s"] > 0 and r["GB/s"] > 0 and r["AI"] > 0
        assert 0.0 < r["attain"] <= 1.0
        assert r["bound"] in ("compute_s", "memory_s", "collective_s")


def test_scope_split_sums_to_bucket_total(profiled):
    """Acceptance: the per-scope cost split (attn / ffn_dense /
    ffn_sparse / logits / sample / other) sums to within 5% of the
    bucket total, and the named scopes alone attribute the bulk of it
    (the cost model parses real dots out of the optimized HLO, it does
    not renormalize)."""
    _, eng = profiled
    for r in eng.profiler.report(eng.tracer.tick_stats):
        split = sum(s["flops"] for s in r["scopes"].values())
        assert split == pytest.approx(r["flops"], rel=0.05)
        assert 0.5 < r["scope_attributed_frac"] <= 1.0
        fracs = {k: s["flops_frac"] for k, s in r["scopes"].items()}
        assert sum(fracs.values()) == pytest.approx(1.0, rel=0.05)
    # the heterogeneity story: decode runs the sparse FFN path, a
    # grouped tick's prefill group the dense one — the split must show it
    rows = {r["bucket"]: r for r in
            eng.profiler.report(eng.tracer.tick_stats)}

    def flops(bucket, scope):
        return rows[bucket]["scopes"].get(scope, {}).get("flops", 0.0)

    assert flops("decode", "ffn_sparse") > flops("decode", "ffn_dense")
    assert flops("mixed_p1", "ffn_dense") > flops("mixed_p1",
                                                  "ffn_sparse")


def test_dot_parser_reads_operand_shapes_by_name():
    """The HLO printer names a dot's operands without their shapes; the
    parser resolves each name to its defining instruction, per
    computation, and attributes the dot to its named scope."""
    import jax.numpy as jnp
    from repro.obs.costmodel import parse_hlo_dot_costs

    def f(x, w, y):
        with jax.named_scope("attn"):
            a = x @ w
        return jnp.einsum("bij,bjk->bik", a.reshape(2, 4, 8), y)

    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((4, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 8, 3), jnp.float32)).compile().as_text()
    costs = parse_hlo_dot_costs(text)
    assert costs["attn"]["flops"] == 2 * 4 * 16 * 16
    assert costs["other"]["flops"] == 2 * 2 * 4 * 3 * 8


def test_active_chip_is_keyed_by_device_kind():
    from repro.roofline import hw
    assert hw.active_chip("TPU v5 lite") is hw.V5E
    assert hw.active_chip("cpu") is hw.CPU_HOST
    assert hw.active_chip() is hw.BY_DEVICE_KIND[
        jax.devices()[0].device_kind]
    with pytest.raises(KeyError):
        hw.active_chip("TPU v4")


def test_greedy_tokens_identical_profile_on_off(nectar):
    """Acceptance: profiling observes, never schedules — greedy output
    is token-identical with --profile on and off."""
    cfg, params = nectar
    prompts = _prompts(cfg, [5, 21, 9])
    off, _ = _serve(cfg, params, prompts)
    on, eng = _serve(cfg, params, prompts,
                     obs=ObsConfig(enabled=True, profile=True))
    assert off == on
    assert eng.profiler is not None


def test_profile_requires_paged_engine(nectar):
    cfg, params = nectar
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, params,
               ServeConfig(max_batch=2, max_seq=64, paged=False,
                           obs=ObsConfig(enabled=True, profile=True)))


# ---------------------------------------------------------------------------
# surfaces: summary, Prometheus, Perfetto counter tracks, the table


def test_summary_and_prometheus_carry_bucket_attainment(profiled):
    _, eng = profiled
    summ = eng.metrics.summary()
    buckets = {r["bucket"]: r for r in summ["bucket_attainment"]}
    assert 0.0 < buckets["decode"]["attain"] <= 1.0
    text = eng.metrics.registry.prometheus_text()
    assert '# TYPE bucket_attainment_attainment gauge' in text
    assert 'bucket_attainment_attainment{bucket="decode"}' in text
    assert 'bucket_attainment_achieved_gflops{bucket="mixed_p1"}' in text


def test_perfetto_counter_tracks_validate(profiled, tmp_path):
    _, eng = profiled
    path = str(tmp_path / "roofline.trace.json")
    write_perfetto(eng.tracer, path, registry=eng.metrics.registry,
                   profiler=eng.profiler)
    want = ["achieved_gflops", "achieved_gbs", "roofline_attainment"]
    assert check_trace.check_perfetto(path, expect_counters=want) == []
    # one sample per profiled tick, numeric values only
    trace = json.load(open(path))
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert len(counters) >= 3
    assert all(isinstance(e["args"]["value"], (int, float))
               for e in counters)
    # and the validator actually gates: a missing expected track fails
    errs = check_trace.check_perfetto(path, expect_counters=["nope"])
    assert errs and "nope" in errs[0]


def test_attainment_table_renders(profiled):
    from repro.obs import attainment_table
    _, eng = profiled
    table = attainment_table(eng.profiler.report(eng.tracer.tick_stats))
    assert "decode" in table and "mixed_p1" in table
    assert "attain" in table and "flops:" in table


def test_example_profile_serve_importable():
    mod = _load("profile_serve_example", "examples/profile_serve.py")
    assert callable(mod.main)


# ---------------------------------------------------------------------------
# perf-regression sentinel


def _committed_index():
    path = os.path.join(_REPO, "benchmarks", "BENCH_quick.json")
    with open(path) as f:
        return json.load(f)


def _committed_baseline():
    path = os.path.join(_REPO, "benchmarks", "baselines", "quick.json")
    with open(path) as f:
        return json.load(f)


def test_bench_compare_clean_on_committed_index():
    """Acceptance: the committed BENCH_quick.json passes against the
    committed baseline (same machine or not)."""
    base = _committed_baseline()
    idx = _committed_index()
    for same_machine in (True, False):
        assert bench_compare.compare(base["suites"], idx, same_machine,
                                     base.get("noise") or {}) == []


def test_bench_compare_fails_20pct_tokens_regression():
    """Acceptance: a synthetic 20% tokens/s drop trips the gate."""
    base = _committed_baseline()
    idx = json.loads(json.dumps(_committed_index()))     # deep copy
    row = idx["bench_serving"]["rows"]["serving_paged_engine"]
    metrics = bench_compare.parse_derived(row)
    old = metrics["tok_s"]
    idx["bench_serving"]["rows"]["serving_paged_engine"] = \
        row.replace(f"tok_s={old:g}", f"tok_s={old * 0.8:.1f}")
    regs = bench_compare.compare(base["suites"], idx, True,
                                 base.get("noise") or {})
    assert regs and any("tok_s" in r and "dropped" in r for r in regs)
    # cross-machine doubling still catches a 20% drop on a 15% band? no
    # — 20% < 30%, by design machine swaps relax throughput too. But a
    # 40% cliff must still fail anywhere:
    idx["bench_serving"]["rows"]["serving_paged_engine"] = \
        row.replace(f"tok_s={old:g}", f"tok_s={old * 0.5:.1f}")
    assert bench_compare.compare(base["suites"], idx, False,
                                 base.get("noise") or {})


def test_bench_compare_directions_floors_and_missing():
    base = {"s": {"r": {"tok_s": 100.0, "p99_ttft_ms": 0.4,
                        "big_ms": 100.0, "identity": 1.0,
                        "ai": 2.3}}}

    def idx(**over):
        m = dict(base["s"]["r"], **over)
        derived = ";".join(f"{k}={v}" for k, v in m.items())
        return {"s": {"rows": {"r": derived}}}

    ok = bench_compare.compare(base, idx(), True, {})
    assert ok == []
    # sub-floor timing swing (0.4ms -> 0.9ms) is jitter, not regression
    assert bench_compare.compare(base, idx(p99_ttft_ms=0.9), True, {}) \
        == []
    # above-floor latency rise gates
    assert bench_compare.compare(base, idx(big_ms=200.0), True, {})
    # ... but not across machines (absolute timings don't transfer)
    assert bench_compare.compare(base, idx(big_ms=200.0), False, {}) \
        == []
    # identity bits are exact
    assert bench_compare.compare(base, idx(identity=0.0), True, {})
    # AI is a static property: informational, never gates
    assert bench_compare.compare(base, idx(ai=9.9), True, {}) == []
    # a vanished row is itself a regression
    assert bench_compare.compare(base, {"s": {"rows": {}}}, True, {})


def test_parse_derived_skips_annotations():
    m = bench_compare.parse_derived(
        "tok_s=105.3;bound=memory_s;ratio=8.38x;identity=True;"
        "target>=1.5x;9.1x;frac=0.25")
    assert m == {"tok_s": 105.3, "ratio": 8.38, "identity": 1.0,
                 "target>": 1.5, "frac": 0.25}


def test_quick_index_records_roofline_skip(monkeypatch, tmp_path):
    """Satellite: --quick records WHY roofline_report is absent (no
    dry-run artifacts) instead of silently omitting it."""
    import benchmarks.run as run_mod
    out = tmp_path / "BENCH_quick.json"
    monkeypatch.setattr(run_mod, "ART_INDEX", str(out))
    monkeypatch.setattr(run_mod, "DRYRUN_DIR", str(tmp_path / "none"))
    run_mod.write_quick_index({"bench_serving": [("row", 1.0, "tok_s=1")]})
    idx = json.loads(out.read_text())
    assert idx["roofline_report"] == {"skipped": "no dryrun artifacts"}
    # with artifacts present, no skip marker is invented
    dr = tmp_path / "dr"
    dr.mkdir()
    (dr / "cell.json").write_text("{}")
    monkeypatch.setattr(run_mod, "DRYRUN_DIR", str(dr))
    run_mod.write_quick_index({"bench_serving": [("row", 1.0, "tok_s=1")]})
    assert "roofline_report" not in json.loads(out.read_text())


def test_committed_baseline_and_history_exist():
    """The sentinel's state is committed: a baseline with fingerprint +
    suites, and at least one append-only history record."""
    base = _committed_baseline()
    assert base["fingerprint"] and base["suites"]
    assert "serving_roofline" in base["suites"]
    hist = os.path.join(_REPO, "benchmarks", "history", "quick.jsonl")
    with open(hist) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    assert recs and all("ts" in r and "fingerprint" in r and "suites" in r
                        for r in recs)


# ---------------------------------------------------------------------------
# percentile edge case (satellite)


def test_percentile_single_sample_window():
    """A one-observation window reports that observation exactly for
    every percentile (p50 == p99 == the sample) — no interpolation
    noise, no index-out-of-range."""
    for q in (0.0, 50.0, 99.0, 100.0):
        assert percentile([7.25], q) == 7.25
    assert percentile([], 50.0) is None
    assert percentile([1.0, 3.0], 100.0) == 3.0
