"""The engine's own tick record against the benchmark's: on a traced CPU
run of the smoke cell, every ``engine.step`` span the engine annotates
nests in one ``bench.tick`` span and matches the ``Tick`` the harness
rebuilt from scheduler state, in step, prefill presence and rows."""

import os

from bench import arrivals, harness
from bench.tests import smoke


def _host_spans(trace_dir, names):
    from jax._src.profiler import ProfileData
    [path] = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
              for f in fs if f.endswith(".xplane.pb")]
    out = {n: {} for n in names}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    st = dict(e.stats)
                    s = int(e.start_ns)
                    out[e.name][int(st["step_num"])] = \
                        (s, s + int(e.duration_ns), st)
    return out


def _rows(stats):
    """The ``rows`` stat as ``(n_tokens, ctx_before, sampled)`` triples."""
    if not stats.get("rows"):
        return []
    return [(int(n), int(c), bool(int(s))) for n, c, s in
            (r.split(":") for r in stats["rows"].split(";"))]


def test_engine_step_spans_match_harness_ticks(tmp_path):
    cell = smoke.cell()
    seed = 2 ** 31 + 21
    params = harness.make_weights(cell, seed)
    eng = harness.make_engine(cell, params, traced=True)
    harness.warm_up(eng, seed)
    offered = arrivals.generate(cell.mix, cell.settings["rate_per_s"], 2.0,
                                seed, cell.conf["vocab_size"])
    win = harness.serve_window(eng, offered, 2.0, harness.CompileCounter(),
                               str(tmp_path))
    spans = _host_spans(tmp_path, ("engine.step", "bench.tick"))
    steps, ticks = spans["engine.step"], spans["bench.tick"]
    assert len(win.ticks) > 10
    assert any(t.has_prefill for t in win.ticks)
    assert any(not t.has_prefill for t in win.ticks)
    assert sorted(steps) == sorted(ticks) == [t.step for t in win.ticks]
    for t in win.ticks:
        s, e, st = steps[t.step]
        bs, be, _ = ticks[t.step]
        assert bs <= s and e <= be
        assert bool(st.get("has_prefill", 0)) == t.has_prefill
        assert sorted(_rows(st)) == sorted(t.rows)
