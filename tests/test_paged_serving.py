"""Paged serving subsystem: block-table KV decode equivalence, chunked
prefill equivalence, scheduler policies, preemption-by-eviction, streaming
API, and metrics sanity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ServeConfig
from repro.models import Model
from repro.serve import api, metrics, paged_kv
from repro.serve.engine import Engine
from repro.serve.scheduler import Request, Scheduler, State


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
    done = eng.run(reqs, max_steps=1000)
    return {i: [int(t) for t in r.tokens_out] for i, r in done.items()}, eng


# ---------------------------------------------------------------------------
# equivalence


def test_paged_decode_token_identical_to_contiguous(nectar):
    """Acceptance: paged greedy output == contiguous-cache engine output on
    a mix of short and long prompts (paging changes memory layout only)."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 37, 9, 60, 3, 21])
    legacy, _ = _serve(cfg, params, prompts, max_batch=3, max_seq=96,
                       paged=False)
    paged, eng = _serve(cfg, params, prompts, max_batch=3, max_seq=96,
                        paged=True, block_size=8, prefill_chunk=16)
    assert set(legacy) == set(paged) == set(range(len(prompts)))
    for i in legacy:
        assert legacy[i] == paged[i], i
    assert eng.pool.n_free == eng.pool.n_blocks  # all blocks returned


def test_chunked_prefill_matches_whole_prompt_logits(nectar):
    """Prefill split into fixed chunks (the prefill group of the one
    forward_step, beside an idle generation group) produces the same
    last-position logits as one whole-prompt forward."""
    cfg, model, params = nectar
    prompt = _prompts(cfg, [29])[0]

    cache = model.init_cache(1, 64, jnp.float32)
    ref, _ = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                           cache)

    bs, MB, nb, C = 8, 8, 16, 8
    pc = model.init_paged_cache(1, nb, bs, MB, jnp.float32)
    tables = np.full((1, MB), nb, np.int32)
    tables[0, :MB] = np.arange(MB)
    pc["block_tables"] = jnp.asarray(tables)
    one = jnp.zeros((1,), jnp.int32)
    pos = 0
    while pos < len(prompt):
        valid = min(C, len(prompt) - pos)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :valid] = prompt[pos:pos + valid]
        _, logits, pc, _ = model.forward_step(
            params, jnp.zeros((1, 1), jnp.int32), jnp.asarray(chunk), pc,
            one, one, jnp.full((1,), pos, jnp.int32),
            jnp.full((1,), valid, jnp.int32), bs)
        last = logits[:, valid - 1]
        pos += valid
    np.testing.assert_allclose(np.asarray(last), np.asarray(ref)[:, 0],
                               rtol=2e-4, atol=2e-4)
    assert int(jnp.argmax(last[0])) == int(jnp.argmax(ref[0, 0]))


# each slot's (phase, tokens in this tick, context before it), on top of
# contexts of 11, 8, 8 and 5 tokens: slot 1 prefills at the chunk seam
# (its second chunk), slot 2 its one-token last chunk; 2 and 3 prefill
# rows pad to the group bucket of 4
_GROUPED_TICKS = {
    1: [("D", 1, 11), ("P", 8, 8), ("D", 1, 8), None],
    2: [("D", 1, 11), ("P", 8, 8), ("P", 1, 8), ("D", 1, 5)],
    3: [("D", 1, 11), ("P", 8, 8), ("P", 1, 8), ("P", 3, 5)],
}


@pytest.fixture(scope="module")
def qwen_smoke():
    cfg = get_config("qwen3-4b-smoke")
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(1))


@pytest.mark.parametrize("n_prefill", [1, 2, 3])
@pytest.mark.parametrize("model_name,backend", [
    ("nectar", "naive"), ("qwen_smoke", "naive"), ("nectar", "flash")])
def test_grouped_tick_matches_phase_by_phase_ticks(request, model_name,
                                                   backend, n_prefill):
    """A tick that runs decode rows at width 1 beside a [P, C] prefill
    group gives the logits, last logits and pool of the same rows run
    phase by phase: a prefill-only tick, then a decode-only tick."""
    from repro.serve.runner import DECODE, PREFILL, ModelRunner
    cfg, model, params = request.getfixturevalue(model_name)
    C = 8
    scfg = ServeConfig(max_batch=4, max_seq=64, paged=True, block_size=8,
                       prefill_chunk=C, attn_backend=backend)
    runner = ModelRunner(model, params, scfg)
    MB = scfg.blocks_per_seq
    tables = np.arange(4 * MB, dtype=np.int32).reshape(4, MB)
    rng = np.random.default_rng(n_prefill)
    ctx = [11, 8, 8, 5]
    seqs = [rng.integers(0, cfg.vocab, n + C, dtype=np.int32) for n in ctx]
    # the contexts, written by prefill ticks
    for pos in range(0, max(ctx), C):
        b = runner.new_batch(C, tables)
        for slot, n in enumerate(ctx):
            if pos < n:
                b.add_row(slot, PREFILL, seqs[slot][pos:min(n, pos + C)],
                          pos)
        runner.step(b)
    cache0 = runner.cache
    rows = _GROUPED_TICKS[n_prefill]

    def tick(kinds):
        """One tick of this case's rows of ``kinds`` ("P", "D")."""
        mine = [(slot, row) for slot, row in enumerate(rows)
                if row is not None and row[0] in kinds]
        n_pf = sum(1 for _, row in mine if row[0] == "P")
        b = runner.new_batch(1, tables, n_prefill=n_pf)
        for slot, (kind, n, start) in mine:
            b.add_row(slot, PREFILL if kind == "P" else DECODE,
                      seqs[slot][start:start + n], start)
        return b, runner.step(b)

    runner.cache = cache0
    bg, grp = tick("PD")
    cache_g = runner.cache
    runner.cache = cache0
    bp, pre = tick("P")
    bd, dec = tick("D")
    assert bg.prefill_bucket == bp.prefill_bucket \
        == {1: 1, 2: 4, 3: 4}[n_prefill]
    assert bg.width == bd.width == 1 and bd.prefill_bucket == 0
    assert bd.tokens.shape == (4, 1) and bg.tokens.shape == (4, C)
    assert grp.prefill_logits.shape[:2] == (bg.prefill_bucket, C)
    tol = dict(rtol=1e-5, atol=1e-5)
    for slot, row in enumerate(rows):
        if row is None:
            continue
        ref = pre if row[0] == "P" else dec
        n = row[1]
        np.testing.assert_allclose(np.asarray(grp.last_logits)[slot],
                                   np.asarray(ref.last_logits)[slot], **tol)
        np.testing.assert_allclose(grp.row_logits(slot)[:n],
                                   ref.row_logits(slot)[:n], **tol)
    for a, b in zip(jax.tree.leaves(cache_g["units"]),
                    jax.tree.leaves(runner.cache["units"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


# ---------------------------------------------------------------------------
# width-1 read path: the whole pool against the gathered tables

_S = 99    # stands for the sentinel block id (the case's n_blocks)
# block tables (3 rows x 4 columns of 4 tokens), each row's lens (its
# query's position) and the pool size; 3 x 4 = 12 > 10 blocks takes the
# pool read, 12 blocks the gather
_POOL_CASES = {
    "f32": ([[0, 1, 2, _S], [3, 4, _S, _S], [5, 6, 7, 8]], [9, 5, 15], 10),
    "int8": ([[0, 1, 2, _S], [3, 4, _S, _S], [5, 6, 7, 8]], [9, 5, 15], 10),
    "shared_blocks": ([[0, 1, 2, _S], [0, 1, 3, _S], [4, 5, _S, _S]],
                      [10, 9, 6], 10),
    "idle_row": ([[0, 1, _S, _S], [_S] * 4, [2, 3, 4, _S]], [6, 0, 11], 10),
    "lens_at_edges": ([[0, _S, _S, _S], [1, 2, _S, _S], [3, 4, 5, 6]],
                      [3, 4, 15], 10),
    "span_equals_pool": ([[0, 1, 2, _S], [3, 4, _S, _S], [5, 6, 7, 8]],
                         [9, 5, 15], 12),
}


@pytest.mark.parametrize("case", sorted(_POOL_CASES))
def test_pool_read_matches_gather_read(qwen_smoke, case):
    """A width-1 group whose tables span more blocks than the pool holds
    attends over the whole pool leaf with a per-row visibility mask; its
    output equals the gathered read's (f32 tolerance), for f32 and int8
    pools, blocks two rows share, and lens at bs - 1, bs and the last
    column. An all-sentinel row gives a finite output. When the tables
    span exactly the pool, the gather stays."""
    from repro.models import attention
    from repro.models.flash import reference_attention
    from repro.serve.kv_cache import quantize_kv
    cfg, _, _ = qwen_smoke
    rows, lens, n_blocks = _POOL_CASES[case]
    bs, B, MB = 4, len(rows), len(rows[0])
    tables = jnp.asarray(np.where(np.asarray(rows) == _S, n_blocks, rows),
                         jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    shape = (n_blocks, bs, cfg.n_kv_heads, cfg.d_head)
    k = jax.random.normal(kk, shape)
    v = jax.random.normal(kv, shape)
    if case == "int8":
        (k8, ks), (v8, vs) = quantize_kv(k, v)
        cache = {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
    else:
        cache = {"k": k, "v": v}
    q = jax.random.normal(kq, (B, 1, cfg.n_heads, cfg.d_head))

    pool = attention.reads_pool(cfg, B, MB, n_blocks, bs, "naive")
    assert pool == (case != "span_equals_pool")
    o = attention._attend_paged(cfg, q, cache, tables, lens, lens[:, None],
                                bs, "naive", q.dtype)
    kg = attention._read_paged(cache, "k", tables, n_blocks)
    vg = attention._read_paged(cache, "v", tables, n_blocks)
    qg = q.reshape(B, 1, cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head)
    ref = reference_attention(qg, kg, vg, causal=False,
                              kv_len=lens + 1).reshape(o.shape)
    live = np.asarray([r != [_S] * MB for r in rows])
    assert np.all(np.isfinite(np.asarray(o)))
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(ref)[live],
                               rtol=1e-5, atol=1e-5)


def _decode_step_hlo(runner):
    """The lowered text of the runner's width-1 decode step."""
    toks, pf, n_valid, *rows = runner.step_args(1, 0, jnp.ones)
    return runner._fn(1, 0).lower(runner.params, toks, pf, runner.cache,
                                  n_valid, *rows).as_text()


@pytest.mark.parametrize("n_kv_blocks,read", [(30, "pool"), (0, "gather")])
def test_pool_read_engine_token_identical_to_contiguous(nectar, n_kv_blocks,
                                                        read):
    """A pool smaller than max_batch x blocks_per_seq (and large enough
    that nothing is preempted): every width-1 group reads the whole pool,
    greedy output equals the contiguous-cache engine's, the engine counts
    each tick with a width-1 group under its read, and the lowered decode
    step gathers no [B, MB*bs, Kv, Dh] K/V. The default pool keeps the
    gather."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 37, 9, 60, 3, 21])
    kw = dict(max_batch=3, max_seq=96, block_size=8, prefill_chunk=16)
    legacy, _ = _serve(cfg, params, prompts, paged=False, **kw)
    paged, eng = _serve(cfg, params, prompts, paged=True,
                        n_kv_blocks=n_kv_blocks, **kw)
    assert eng.sched.n_preemptions == 0
    assert legacy == paged
    assert eng.runner.kv_read == read
    # every step of this engine has a width-1 group (decode or grouped)
    reads = eng.metrics.registry.labeled_counter(
        "engine_decode_kv_reads_total", "read").values
    assert reads == {read: eng.runner.n_steps} and eng.runner.n_steps > 0
    B, MB, bs = 3, eng.scfg.blocks_per_seq, 8
    gathered = f"tensor<{B}x{MB * bs}x{cfg.n_kv_heads}x{cfg.d_head}xf32>"
    assert (gathered in _decode_step_hlo(eng.runner)) == (read == "gather")


def test_grouped_engine_token_identical_to_contiguous(nectar):
    """Overlapping prompts on four slots: ticks with one to four prefill
    rows run grouped, and greedy output equals the contiguous-cache
    engine's."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 37, 9, 60, 3, 21, 30, 14], seed=11)
    legacy, _ = _serve(cfg, params, prompts, max_batch=4, max_seq=96,
                       paged=False)
    paged, eng = _serve(cfg, params, prompts, max_batch=4, max_seq=96,
                        paged=True, block_size=8, prefill_chunk=16)
    assert legacy == paged
    mixed = eng.metrics.registry.labeled_counter(
        "engine_mixed_ticks_total", "prefill_bucket").values
    assert set(mixed) == {"1", "4"}


def test_preemption_on_block_exhaustion_preserves_output(nectar):
    """Pool too small for both requests: the scheduler evicts and replays,
    and greedy output is unchanged vs an unconstrained pool."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [12, 14], seed=3)
    free, _ = _serve(cfg, params, prompts, max_new=16, max_batch=2,
                     max_seq=64, paged=True, block_size=4, prefill_chunk=8)
    tight, eng = _serve(cfg, params, prompts, max_new=16, max_batch=2,
                        max_seq=64, paged=True, block_size=4,
                        n_kv_blocks=10, prefill_chunk=8)
    assert eng.metrics.evictions > 0
    assert eng.sched.n_preemptions > 0
    assert free == tight
    assert eng.pool.n_free == eng.pool.n_blocks


def test_pool_too_small_for_single_request_raises(nectar):
    cfg, _, params = nectar
    eng = Engine(cfg, params,
                 ServeConfig(max_batch=1, max_seq=64, paged=True,
                             block_size=4, n_kv_blocks=2, prefill_chunk=8))
    eng.add_request(Request(rid=0, prompt=_prompts(cfg, [20])[0],
                            max_new=4))
    with pytest.raises(RuntimeError, match="KV pool too small"):
        for _ in range(50):
            eng.step()


# ---------------------------------------------------------------------------
# paged_kv manager


def test_paged_kv_alloc_free_defrag(nectar):
    cfg, _, _ = nectar
    pool = paged_kv.PagedKVCache(cfg, n_blocks=8, block_size=4, max_batch=2,
                                 max_blocks_per_seq=4)
    assert pool.allocate(0, 9)            # 3 blocks
    assert pool.allocate(1, 5)            # 2 blocks
    assert pool.n_free == 3
    assert pool.allocate(0, 12)           # grow to 3 (no-op) then...
    assert not pool.allocate(0, 17)       # ...17 tokens > 4-block table row
    assert pool.free_slot(0) == 3
    assert pool.free_slot(0) == 0         # idempotent
    # slot 1 owns blocks [3, 4]; defrag compacts them to [0, 1]
    perm = pool.defrag()
    assert perm is not None
    assert pool.owned[1] == [0, 1]
    assert list(perm[:2]) == [3, 4]       # new row i reads old row perm[i]
    assert pool.tables()[1, 0] == 0 and pool.tables()[1, 1] == 1
    assert sorted(pool.free) == list(range(2, 8))
    assert pool.defrag() is None          # already compact


def test_paged_kv_byte_accounting(nectar):
    cfg, _, _ = nectar
    fp16 = paged_kv.kv_bytes_per_token(cfg, int8_kv=False)
    int8 = paged_kv.kv_bytes_per_token(cfg, int8_kv=True)
    # 6 attn layers * 2 (K+V) * 4 kv heads * 32 d_head * 2B
    assert fp16 == 6 * 2 * 4 * 32 * 2
    assert int8 < fp16                    # int8 halves elements, adds scales
    pool = paged_kv.PagedKVCache(cfg, n_blocks=4, block_size=8, max_batch=1,
                                 max_blocks_per_seq=4)
    pool.allocate(0, 10)                  # 2 blocks
    assert pool.used_bytes() == 2 * 8 * fp16
    assert pool.capacity_bytes() == 4 * 8 * fp16


def test_engine_defrag_mid_flight_is_transparent(nectar):
    """Finish one request (leaves holes), defrag, keep decoding: output of
    the surviving request is unchanged vs a no-defrag run."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [10, 22], seed=5)

    def run(defrag_at):
        eng = Engine(cfg, params,
                     ServeConfig(max_batch=2, max_seq=64, paged=True,
                                 block_size=4, prefill_chunk=32))
        eng.add_request(Request(rid=0, prompt=prompts[0], max_new=4))
        eng.add_request(Request(rid=1, prompt=prompts[1], max_new=24))
        for i in range(200):
            if i == defrag_at:
                eng.defrag()
            if not eng._busy():
                break
            eng.step()
        return [int(t) for t in eng._requests[1].tokens_out]

    assert run(defrag_at=-1) == run(defrag_at=12)


# ---------------------------------------------------------------------------
# scheduler policies + admission control


def test_priority_policy_orders_admission(nectar):
    cfg, _, _ = nectar
    scfg = ServeConfig(max_batch=1, max_seq=32, paged=True, block_size=4,
                       policy="priority")
    pool = paged_kv.PagedKVCache(cfg, scfg.pool_blocks, scfg.block_size, 1,
                                 scfg.blocks_per_seq)
    sched = Scheduler(scfg, pool)
    for rid, pr in [(0, 0), (1, 5), (2, 1)]:
        sched.submit(Request(rid=rid, prompt=np.arange(4, dtype=np.int32),
                             priority=pr))
    admitted = sched.admit()
    assert [e.req.rid for e in admitted] == [1]   # highest priority first
    assert [e.req.rid for e in sched.waiting] == [2, 0]


def test_admission_control_bounds_queue(nectar):
    cfg, _, _ = nectar
    scfg = ServeConfig(max_batch=1, max_seq=32, paged=True, max_queue=2)
    pool = paged_kv.PagedKVCache(cfg, scfg.pool_blocks, scfg.block_size, 1,
                                 scfg.blocks_per_seq)
    sched = Scheduler(scfg, pool)
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32))
            for i in range(4)]
    assert sched.submit(reqs[0]) and sched.submit(reqs[1])
    assert not sched.submit(reqs[2])              # queue bound hit
    assert sched.n_rejected == 1
    sched.admit()                                 # drains one into a slot
    assert sched.submit(reqs[3])


def test_unknown_policy_rejected(nectar):
    cfg, _, _ = nectar
    scfg = ServeConfig(paged=True, policy="lifo")
    pool = paged_kv.PagedKVCache(cfg, 4, 4, 1, 4)
    with pytest.raises(ValueError, match="policy"):
        Scheduler(scfg, pool)


def test_paged_cache_rejects_recurrent_families():
    cfg = get_config("zamba2-smoke")
    model = Model(cfg)
    with pytest.raises(ValueError, match="attention-only"):
        model.init_paged_cache(1, 4, 4, 4, jnp.float32)


# ---------------------------------------------------------------------------
# streaming API + metrics


def test_streaming_generate_matches_batch_run(nectar):
    cfg, _, params = nectar
    prompt = _prompts(cfg, [11], seed=7)[0]
    batch, _ = _serve(cfg, params, [prompt], max_new=6, max_batch=2,
                      max_seq=64, paged=True, block_size=8)
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_seq=64,
                                          paged=True, block_size=8))
    streamed = [int(t) for t in api.generate(eng, prompt, max_new=6)]
    assert streamed == batch[0]


def test_streaming_server_multiplexes(nectar):
    cfg, _, params = nectar
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_seq=64,
                                          paged=True, block_size=8,
                                          prefill_chunk=16))
    srv = api.StreamingServer(eng)
    rids = [srv.submit(p, max_new=5)
            for p in _prompts(cfg, [6, 18, 9], seed=9)]
    done = srv.drain()
    assert sorted(done) == sorted(rids)
    for r in done.values():
        assert len(r.tokens_out) == 5


def test_concurrent_servers_never_collide_rids(nectar):
    """Regression: rids come from the engine's counter. Two front-ends on
    one engine (an abandoned generate() stream + a fresh StreamingServer)
    used to both start at rid 0, silently overwriting the in-flight
    scheduler entry and leaking its slot and blocks."""
    cfg, _, params = nectar
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_seq=64,
                                          paged=True, block_size=8,
                                          prefill_chunk=16))
    g = api.generate(eng, _prompts(cfg, [8], seed=1)[0], max_new=12)
    next(g)                               # request in flight, then abandon
    del g
    srv = api.StreamingServer(eng)
    rid = srv.submit(_prompts(cfg, [6], seed=2)[0], max_new=4)
    done = srv.drain()
    assert rid in done
    assert not eng._busy()
    assert eng.pool.n_free == eng.pool.n_blocks     # nothing leaked
    assert eng.pool.owned == {}
    # duplicate in-flight rid is rejected loudly, not silently overwritten
    assert eng.add_request(Request(rid=77, prompt=np.arange(4, dtype=np.int32),
                                   max_new=8))
    with pytest.raises(ValueError, match="already in flight"):
        eng.add_request(Request(rid=77, prompt=np.arange(4, dtype=np.int32),
                                max_new=2))


def test_unservable_prompt_cannot_wedge_server(nectar):
    """Regression: a prompt longer than max_seq is rejected at submit();
    one force-fed past the engine is shed on the first idle poll instead
    of pinning busy=True forever."""
    cfg, _, params = nectar
    eng = Engine(cfg, params, ServeConfig(max_batch=1, max_seq=16,
                                          paged=True, block_size=8))
    srv = api.StreamingServer(eng)
    with pytest.raises(ValueError, match="max_seq"):
        srv.submit(np.arange(40, dtype=np.int32), max_new=4)
    # engine-level: add_request refuses instead of crashing/looping
    assert not eng.add_request(Request(
        rid=0, prompt=np.arange(40, dtype=np.int32), max_new=4))
    # a servable request still goes through afterwards
    rid = srv.submit(np.arange(6, dtype=np.int32), max_new=4)
    done = srv.drain(max_steps=200)
    assert rid in done and not srv.busy


def test_legacy_engine_max_new_1_matches_paged(nectar):
    """Regression: the slot path used to append a decode token past
    max_new=1; both modes must emit exactly the prefill token."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [7])
    legacy, _ = _serve(cfg, params, prompts, max_new=1, max_batch=2,
                       max_seq=32, paged=False)
    paged, _ = _serve(cfg, params, prompts, max_new=1, max_batch=2,
                      max_seq=32, paged=True, block_size=8)
    assert len(legacy[0]) == len(paged[0]) == 1
    assert legacy == paged


def test_result_forget_releases_engine_state(nectar):
    cfg, _, params = nectar
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_seq=64,
                                          paged=True, block_size=8))
    srv = api.StreamingServer(eng)
    rid = srv.submit(_prompts(cfg, [6])[0], max_new=3)
    srv.drain()
    assert rid in eng._requests and rid in eng.metrics.requests
    req = srv.result(rid, forget=True)
    assert req is not None and len(req.tokens_out) == 3
    assert rid not in eng._requests and rid not in eng.metrics.requests
    assert srv.result(rid) is None


def test_metrics_ttft_le_latency(nectar):
    cfg, _, params = nectar
    _, eng = _serve(cfg, params, _prompts(cfg, [8, 40, 12]), max_new=6,
                    max_batch=2, max_seq=64, paged=True, block_size=8,
                    prefill_chunk=16)
    s = eng.metrics.summary()
    assert s["n_finished"] == 3
    assert s["generated_tokens"] == 18
    assert s["tokens_per_s"] > 0
    for r in eng.metrics.requests.values():
        assert r.ttft is not None and r.latency is not None
        assert 0 <= r.ttft <= r.latency
        if r.tpot is not None:
            assert r.tpot >= 0
    assert s["ttft_p50_ms"] <= s["ttft_p99_ms"]
    assert s["latency_p50_ms"] <= s["latency_p99_ms"]


def test_traffic_counters_match_legacy_accounting(nectar):
    """metrics.traffic_step is the lifted Engine._account: same numbers
    the seed engine reported (weight bytes halve-ish under sparsity)."""
    cfg, _, _ = nectar
    scfg_d = ServeConfig(sparse_decode=False)
    scfg_s = ServeConfig(sparse_decode=True)
    dense = metrics.traffic_step(cfg, scfg_d, 4)
    sparse = metrics.traffic_step(cfg, scfg_s, 4)
    assert dense.sparse_savings_bytes == 0
    assert sparse.sparse_savings_bytes > 0
    assert sparse.weight_bytes + sparse.sparse_savings_bytes \
        == pytest.approx(dense.weight_bytes)
    assert dense.kv_bytes == sparse.kv_bytes > 0
