"""Speculative decoding subsystem (repro.spec): drafter correctness,
greedy token-identity vs the non-speculative paged engine, rejection-
sampling distribution match, adaptive K, paged-KV fork/rollback
(truncate, defrag pinning), int8 KV through the paged pool, and
preemption-by-recompute interacting with speculation."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ObsConfig, ServeConfig, SpecConfig
from repro.models import Model
from repro.serve import api, paged_kv
from repro.serve.engine import Engine
from repro.serve.scheduler import Request
from repro.spec import (AdaptiveK, ModelDrafter, NGramDrafter,
                        SelfSpecDrafter, greedy_accept, rejection_accept)


@pytest.fixture(scope="module")
def nectar():
    cfg = get_config("nectar-relu-llama-1.7m")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def draft(nectar):
    dcfg = get_config("nectar-relu-llama-draft")
    return dcfg, Model(dcfg).init(jax.random.PRNGKey(7))


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=int(n), dtype=np.int32)
            for n in lengths]


def _serve(cfg, params, prompts, max_new=10, drafter=None,
           draft_params=None, **scfg_kw):
    eng = Engine(cfg, params, ServeConfig(**scfg_kw), drafter=drafter,
                 draft_params=draft_params)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    done = eng.run(reqs, max_steps=2000)
    return {i: [int(t) for t in r.tokens_out] for i, r in done.items()}, eng


# ---------------------------------------------------------------------------
# greedy equivalence: every drafter, token-identical to the paged baseline


def _base_kw():
    return dict(max_batch=3, max_seq=96, paged=True, block_size=8,
                prefill_chunk=16)


def test_greedy_spec_ngram_token_identical(nectar):
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 23, 9, 40])
    base, _ = _serve(cfg, params, prompts, **_base_kw())
    spec, eng = _serve(cfg, params, prompts,
                       spec=SpecConfig(drafter="ngram", k=4, k_max=6),
                       **_base_kw())
    assert base == spec
    s = eng.metrics.summary()
    assert s["spec_steps"] > 0
    assert eng.pool.n_free == eng.pool.n_blocks


def test_verify_rows_beside_prefill_group_run_at_k_plus_1(nectar):
    """Staggered prompts: ticks that verify some rows while others
    prefill run the verify rows at k_max+1 beside the prefill group (the
    executable's name says so) instead of padding them to the chunk, and
    greedy output equals the non-speculative engine's."""
    cfg, _, params = nectar
    # prefill_chunk 11: a shape no other test compiles, so this engine
    # counts its compiles
    kw = dict(_base_kw(), prefill_chunk=11)
    prompts = _prompts(cfg, [5, 40, 70], seed=6)
    base, _ = _serve(cfg, params, prompts, **kw)
    spec, eng = _serve(cfg, params, prompts,
                       spec=SpecConfig(drafter="selfspec", k=3, k_max=4),
                       obs=ObsConfig(enabled=True), **kw)
    assert base == spec
    both = [t for t in eng.tracer.tick_stats
            if t.get("rows_verify") and t.get("rows_prefill")]
    assert any(t["gen_width"] == 5 for t in both)
    assert all(t["width"] == 11 for t in both)
    assert "jit(serve_step_w5_p1x11)" in set(eng.metrics.compiles.values)
    assert eng.pool.n_free == eng.pool.n_blocks


def test_greedy_spec_model_drafter_token_identical(nectar, draft):
    """A random-init draft model accepts ~nothing — output must STILL be
    token-identical (speculation changes cost, never content)."""
    cfg, _, params = nectar
    dcfg, dparams = draft
    prompts = _prompts(cfg, [5, 23], seed=1)
    base, _ = _serve(cfg, params, prompts, **_base_kw())
    spec, eng = _serve(
        cfg, params, prompts, draft_params=dparams,
        spec=SpecConfig(drafter="model", k=3, k_max=4,
                        draft_name="nectar-relu-llama-draft"),
        **_base_kw())
    assert base == spec
    assert eng.pool.n_free == eng.pool.n_blocks


def test_greedy_spec_selfspec_token_identical(nectar):
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 23], seed=2)
    base, _ = _serve(cfg, params, prompts, **_base_kw())
    spec, eng = _serve(cfg, params, prompts,
                       spec=SpecConfig(drafter="selfspec", k=3, k_max=4),
                       **_base_kw())
    assert base == spec
    assert eng.pool.n_free == eng.pool.n_blocks


def test_verify_step_matches_sequential_decode(nectar):
    """Model-level acceptance: one K+1-position VERIFY row of the
    forward_step's generation group produces the same logits chain as
    feeding the tokens one DECODE row at a time."""
    cfg, model, params = nectar
    bs, MB, nb = 8, 8, 16
    prompt = _prompts(cfg, [13], seed=4)[0]
    toks = _prompts(cfg, [4], seed=5)[0]         # pending + 3 "drafts"
    P = len(prompt)
    zero = jnp.zeros((1,), jnp.int32)

    def fresh():
        c = model.init_paged_cache(1, nb, bs, MB, jnp.float32)
        tables = np.full((1, MB), nb, np.int32)
        tables[0] = np.arange(MB)
        c["block_tables"] = jnp.asarray(tables)
        _, _, c, _ = model.forward_step(
            params, jnp.zeros((1, 1), jnp.int32),
            jnp.asarray(np.pad(prompt, (0, 16 - P))[None]), c, zero, zero,
            zero, jnp.full((1,), P, jnp.int32), bs)
        return c

    cache = fresh()
    cache["lens"] = jnp.full((1,), P, jnp.int32)
    v_logits, _, _, _ = model.forward_step(
        params, jnp.asarray(toks[None]), None, cache,
        jnp.full((1,), len(toks), jnp.int32), None, None, None, bs)

    cache = fresh()
    seq = []
    for i, t in enumerate(toks):
        cache["lens"] = jnp.full((1,), P + i, jnp.int32)
        lg, _, cache, _ = model.forward_step(
            params, jnp.asarray([[t]]), None, cache,
            jnp.ones((1,), jnp.int32), None, None, None, bs)
        seq.append(np.asarray(lg)[0, 0])
    np.testing.assert_allclose(np.asarray(v_logits)[0], np.stack(seq),
                               rtol=2e-4, atol=2e-4)
    assert list(np.asarray(v_logits)[0].argmax(-1)) \
        == [int(s.argmax()) for s in seq]


# ---------------------------------------------------------------------------
# acceptance math


def test_greedy_accept_prefix_and_correction():
    emitted, a = greedy_accept(np.array([7, 8, 9]),
                               np.array([7, 8, 3, 5]))
    assert emitted == [7, 8, 3] and a == 2      # correction at divergence
    emitted, a = greedy_accept(np.array([7, 8, 9]),
                               np.array([7, 8, 9, 5]))
    assert emitted == [7, 8, 9, 5] and a == 3   # all accepted + bonus
    emitted, a = greedy_accept(np.array([], np.int32), np.array([4]))
    assert emitted == [4] and a == 0            # no drafts == plain decode


def test_rejection_sampling_matches_target_distribution():
    """Acceptance criterion: the first emitted token of a spec step is
    marginally distributed EXACTLY as the target p, whatever the draft
    proposal q says (Leviathan et al. guarantee)."""
    rng = np.random.default_rng(0)
    V, T, n = 6, 1.0, 40000
    logits = np.array([[2.0, 1.0, 0.0, -1.0, 0.5, -2.0],
                       [0.0, 0.0, 0.0, 0.0, 0.0, 0.0]])
    from repro.spec.accept import softmax
    p = softmax(logits[0], T)
    q = np.array([0.05, 0.6, 0.05, 0.1, 0.1, 0.1])   # deliberately off

    counts = np.zeros(V)
    for _ in range(n):
        d = rng.choice(V, p=q)
        emitted, _ = rejection_accept(rng, np.array([d]), q[None],
                                      logits, T)
        counts[emitted[0]] += 1
    emp = counts / n
    assert np.abs(emp - p).max() < 0.01          # ~4 sigma at n=40k

    # deterministic (one-hot) drafter is also distribution-correct
    counts = np.zeros(V)
    for _ in range(n):
        emitted, _ = rejection_accept(rng, np.array([1]), None, logits, T)
        counts[emitted[0]] += 1
    assert np.abs(counts / n - p).max() < 0.01


# ---------------------------------------------------------------------------
# drafters


def test_ngram_drafter_proposes_continuation():
    d = NGramDrafter(n=3)
    ctx = np.array([5, 6, 7, 8, 9, 1, 2, 5, 6, 7], np.int32)
    toks, q = d.propose(0, ctx, 4)
    assert list(toks) == [8, 9, 1, 2] and q is None
    toks, _ = d.propose(0, np.array([1, 2, 3], np.int32), 4)
    assert len(toks) == 0                        # no repeat -> no bet


def test_model_drafter_resyncs_after_rollback(nectar, draft):
    """The drafter's per-request cache survives arbitrary commit/rollback:
    proposals after a diverging commit equal a fresh drafter's."""
    cfg, _, params = nectar
    dcfg, dparams = draft
    ctx = _prompts(cfg, [9], seed=6)[0]
    d1 = ModelDrafter(dcfg, dparams, max_seq=64)
    t1, _ = d1.propose(0, ctx, 3)
    # engine committed something other than the drafts
    ctx2 = np.concatenate([ctx, np.array([11, 12], np.int32)])
    t2, _ = d1.propose(0, ctx2, 3)
    fresh = ModelDrafter(dcfg, dparams, max_seq=64)
    t3, _ = fresh.propose(0, ctx2, 3)
    assert list(t2) == list(t3)
    d1.forget(0)
    assert 0 not in d1._caches


def test_selfspec_requires_attention_stack():
    cfg = get_config("zamba2-smoke")
    with pytest.raises(ValueError, match="attention"):
        SelfSpecDrafter(cfg, None, 64)


# ---------------------------------------------------------------------------
# adaptive K


def test_adaptive_k_backs_off_and_recovers():
    spec = SpecConfig(k=4, k_min=1, k_max=6, accept_low=0.4,
                      accept_high=0.7, ema_decay=0.5)
    ctl = AdaptiveK.from_config(spec)
    for _ in range(8):
        ctl.update(0.0)
    assert ctl.k == spec.k_min                   # collapsed acceptance
    for _ in range(12):
        ctl.update(1.0)
    assert ctl.k == spec.k_max                   # and grows back, capped


def test_adaptive_k_steers_engine(nectar):
    """Highly repetitive prompts: the n-gram drafter is nearly always
    right, so the engine's K must climb above its starting value."""
    cfg, _, params = nectar
    pat = np.tile(np.array([3, 1, 4, 1, 5], np.int32), 8)
    _, eng = _serve(cfg, params, [pat], max_new=24,
                    spec=SpecConfig(drafter="ngram", k=1, k_max=6,
                                    ema_decay=0.5),
                    **_base_kw())
    assert eng.kctl.k > 1
    assert eng.metrics.summary()["spec_acceptance_rate"] > 0.5


# ---------------------------------------------------------------------------
# paged-KV fork/rollback


def test_truncate_frees_tail_and_is_idempotent(nectar):
    cfg, _, _ = nectar
    pool = paged_kv.PagedKVCache(cfg, n_blocks=8, block_size=4, max_batch=2,
                                 max_blocks_per_seq=6)
    assert pool.allocate(0, 18)                  # 5 blocks, partial tail
    assert pool.n_free == 3
    assert pool.truncate(0, 10) == 2             # keep ceil(10/4)=3 blocks
    assert pool.n_free == 5
    assert pool.truncate(0, 10) == 0             # idempotent partial tail
    assert pool.truncate(0, 9) == 0              # same block count: no-op
    assert list(pool.tables()[0, 3:]) == [8, 8, 8]
    assert pool.truncate(0, 0) == 3              # full rollback
    assert pool.truncate(1, 5) == 0              # unknown slot: no-op
    assert pool.n_free == 8
    # rollback then re-extend reuses the pool cleanly
    assert pool.allocate(0, 18)
    assert pool.n_free == 3


def test_defrag_never_moves_pinned_blocks(nectar):
    """A slot mid-verify has its physical block ids captured inside an
    in-flight device block table — defrag must compact around them."""
    cfg, _, _ = nectar
    pool = paged_kv.PagedKVCache(cfg, n_blocks=8, block_size=4, max_batch=3,
                                 max_blocks_per_seq=4)
    pool.allocate(0, 8)                          # blocks [0, 1]
    pool.allocate(1, 8)                          # blocks [2, 3]
    pool.allocate(2, 4)                          # block  [4]
    pool.free_slot(0)                            # holes at [0, 1]
    pool.pin(1)
    perm = pool.defrag()
    assert pool.owned[1] == [2, 3]               # pinned: untouched
    assert pool.owned[2] == [0]                  # compacted into a hole
    assert perm[0] == 4
    assert list(perm[2:4]) == [2, 3]             # pinned rows map to self
    assert sorted(pool.free) == [1, 4, 5, 6, 7]
    pool.unpin(1)
    pool.defrag()
    assert pool.owned[1] == [1, 2]               # movable again after unpin


# ---------------------------------------------------------------------------
# preemption-by-recompute x speculation


def test_preempted_spec_request_emits_identical_tokens(nectar):
    """A pool too small for both requests forces evict+replay mid-
    speculation; greedy output must equal both the unconstrained spec run
    and the non-speculative baseline."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [12, 14], seed=3)
    kw = dict(max_batch=2, max_seq=64, paged=True, block_size=4,
              prefill_chunk=8)
    sp = SpecConfig(drafter="ngram", k=4, k_max=6)
    base, _ = _serve(cfg, params, prompts, max_new=16, **kw)
    free, _ = _serve(cfg, params, prompts, max_new=16, spec=sp, **kw)
    tight, eng = _serve(cfg, params, prompts, max_new=16, spec=sp,
                        n_kv_blocks=10, **kw)
    assert eng.sched.n_preemptions > 0
    assert base == free == tight
    assert eng.pool.n_free == eng.pool.n_blocks


# ---------------------------------------------------------------------------
# int8 KV end-to-end through the paged pool


def test_int8_kv_pool_accounting_matches_device(nectar):
    cfg, model, _ = nectar
    scfg = ServeConfig(max_batch=2, max_seq=64, paged=True, block_size=8,
                       kv_quant=True)
    cache = model.init_paged_cache(2, scfg.pool_blocks, 8,
                                   scfg.blocks_per_seq, jnp.float32,
                                   int8_kv=True)
    dev = sum(leaf.nbytes for leaf in jax.tree.leaves(cache["units"]))
    per_tok = dev / (scfg.pool_blocks * scfg.block_size)
    assert per_tok == paged_kv.kv_bytes_per_token(cfg, int8_kv=True)
    assert paged_kv.kv_bytes_per_token(cfg, int8_kv=True) \
        < paged_kv.kv_bytes_per_token(cfg, int8_kv=False)


def test_int8_kv_decode_equivalence_within_tolerance(nectar):
    """Same prompt through an fp32 pool and an int8 pool: decode logits
    agree within per-(token, head) int8 quantization error."""
    cfg, model, params = nectar
    bs, MB, nb = 8, 8, 16
    prompt = _prompts(cfg, [21], seed=8)[0]

    def decode_logits(int8):
        c = model.init_paged_cache(1, nb, bs, MB, jnp.float32,
                                   int8_kv=int8)
        tables = np.full((1, MB), nb, np.int32)
        tables[0] = np.arange(MB)
        c["block_tables"] = jnp.asarray(tables)
        P = len(prompt)
        zero = jnp.zeros((1,), jnp.int32)
        _, _, c, _ = model.forward_step(
            params, jnp.zeros((1, 1), jnp.int32),
            jnp.asarray(np.pad(prompt, (0, 32 - P))[None]), c, zero, zero,
            zero, jnp.full((1,), P, jnp.int32), bs)
        c["lens"] = jnp.full((1,), P, jnp.int32)
        lg, _, _, _ = model.forward_step(
            params, jnp.asarray([[5]]), None, c, jnp.ones((1,), jnp.int32),
            None, None, None, bs)
        return np.asarray(lg)[0, 0]

    fp = decode_logits(False)
    q8 = decode_logits(True)
    scale = np.abs(fp).max()
    assert np.abs(q8 - fp).max() < 0.05 * scale
    assert int(fp.argmax()) == int(q8.argmax())


def test_int8_kv_paged_serving_end_to_end(nectar):
    """kv_quant=True through the full paged engine (prefill, decode,
    speculation): runs to completion, frees every block, and greedy
    output stays token-identical for this model/seed (quantization error
    is far below its logit margins)."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 23], seed=9)
    fp, _ = _serve(cfg, params, prompts, **_base_kw())
    q8, eng = _serve(cfg, params, prompts, kv_quant=True, **_base_kw())
    assert sorted(q8) == sorted(fp)
    match = sum(a == b for i in fp for a, b in zip(fp[i], q8[i]))
    total = sum(len(v) for v in fp.values())
    assert match / total > 0.5                   # tolerance, not identity
    sp, eng2 = _serve(cfg, params, prompts, kv_quant=True,
                      spec=SpecConfig(drafter="ngram", k=3, k_max=4),
                      **_base_kw())
    assert q8 == sp                              # spec identity holds @ int8
    assert eng.pool.n_free == eng.pool.n_blocks
    assert eng2.pool.n_free == eng2.pool.n_blocks


# ---------------------------------------------------------------------------
# API + metrics


def test_streaming_generate_with_drafter(nectar):
    cfg, _, params = nectar
    prompt = _prompts(cfg, [11], seed=7)[0]
    batch, _ = _serve(cfg, params, [prompt], max_new=6, **_base_kw())
    eng = Engine(cfg, params,
                 ServeConfig(spec=SpecConfig(drafter="ngram", k=3, k_max=4),
                             **_base_kw()))
    streamed = [int(t) for t in api.generate(eng, prompt, max_new=6)]
    assert streamed == batch[0]


def test_spec_metrics_counters(nectar):
    cfg, _, params = nectar
    pat = np.tile(np.array([3, 1, 4, 1, 5], np.int32), 6)
    _, eng = _serve(cfg, params, [pat], max_new=16,
                    spec=SpecConfig(drafter="ngram", k=4, k_max=6),
                    **_base_kw())
    s = eng.metrics.summary()
    assert s["spec_steps"] > 0
    assert 0.0 <= s["spec_acceptance_rate"] <= 1.0
    assert s["spec_tokens_per_verify"] > 1.0     # repetitive text amortizes
    assert s["generated_tokens"] == 16
    m = eng.metrics
    assert m.spec_accepted <= m.spec_drafted
    assert m.spec_emitted >= m.spec_steps        # >= 1 token per verify


def test_drafter_weight_stream_is_counted(nectar, draft):
    """Table-II honesty: model/selfspec drafters stream their own weights
    per draft step; ngram streams nothing."""
    cfg, _, params = nectar
    dcfg, dparams = draft
    scfg = ServeConfig(**_base_kw())
    assert NGramDrafter().weight_bytes_per_step(scfg) == 0.0
    md = ModelDrafter(dcfg, dparams, max_seq=96)
    per_step = md.weight_bytes_per_step(scfg)
    assert per_step > 0
    prompts = _prompts(cfg, [9], seed=11)
    _, eng_ng = _serve(cfg, params, prompts,
                       spec=SpecConfig(drafter="ngram", k=3, k_max=4),
                       **_base_kw())
    _, eng_md = _serve(cfg, params, prompts, draft_params=dparams,
                       spec=SpecConfig(drafter="model", k=3, k_max=4,
                                       draft_name="nectar-relu-llama-draft"),
                       **_base_kw())
    # same target weights per verify pass + a nonzero draft stream on top
    assert eng_md._draft_steps_seen > 0
    w_md = eng_md.metrics.summary()["weight_bytes"]
    ver_md = eng_md.metrics.spec_steps
    w_ng = eng_ng.metrics.summary()["weight_bytes"]
    assert w_md > ver_md * per_step * 0.9       # draft stream included
    assert eng_ng._draft_steps_seen == 0
    assert w_ng > 0


def test_spec_requires_paged_engine(nectar):
    cfg, _, params = nectar
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, params,
               ServeConfig(paged=False, spec=SpecConfig()))


def test_spec_rejects_codebook_models():
    cfg = get_config("musicgen-smoke")
    with pytest.raises(ValueError, match="codebooks|token streams"):
        Engine(cfg, None, ServeConfig(paged=True, spec=SpecConfig()))
