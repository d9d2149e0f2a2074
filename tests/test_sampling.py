"""SamplingParams + batched sampling + the unified ModelRunner step:
filter math (top-k/top-p/repetition penalty), greedy bit-equivalence,
stop-sequence truncation, max_tokens vs paged rollback, per-request
reproducibility, the flash attention backend, and batched drafting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ServeConfig, SpecConfig
from repro.models import Model
from repro.serve import api, sampling
from repro.serve.engine import Engine
from repro.serve.runner import DECODE, PREFILL, ModelRunner
from repro.serve.sampling import Sampler, SamplingParams
from repro.serve.scheduler import Request
from repro.spec import ModelDrafter


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


def _serve(cfg, params, prompts, max_new=8, sampling_params=None,
           **scfg_kw):
    eng = Engine(cfg, params, ServeConfig(**scfg_kw))
    sp = sampling_params or SamplingParams()
    reqs = [Request(rid=i, prompt=p, max_new=max_new, sampling=sp)
            for i, p in enumerate(prompts)]
    done = eng.run(reqs, max_steps=1000)
    return {i: [int(t) for t in r.tokens_out] for i, r in done.items()}, eng


def _kw(**over):
    kw = dict(max_batch=2, max_seq=64, paged=True, block_size=8,
              prefill_chunk=16)
    kw.update(over)
    return kw


# ---------------------------------------------------------------------------
# SamplingParams validation


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError, match="repetition_penalty"):
        SamplingParams(repetition_penalty=0.0)
    with pytest.raises(ValueError, match="max_tokens"):
        SamplingParams(max_tokens=0)
    with pytest.raises(ValueError, match="stop"):
        SamplingParams(stop=((),))
    sp = SamplingParams(stop=[[1, 2], (3,)], temperature=-1.0)
    assert sp.stop == ((1, 2), (3,)) and sp.is_greedy


# ---------------------------------------------------------------------------
# batched sampler math


def _arrays(B, **over):
    a = dict(temp=np.zeros((B,), np.float32),
             top_k=np.zeros((B,), np.int32),
             top_p=np.ones((B,), np.float32),
             rep=np.ones((B,), np.float32),
             presence=np.zeros((B, 8), bool),
             keys=np.stack([sampling.request_key(0, r, 0)
                            for r in range(B)]))
    a.update(over)
    return a


def test_greedy_sampler_is_argmax():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4, 8)).astype(np.float32)
    a = _arrays(4)
    tok, lp = Sampler()(jnp.asarray(logits), a["presence"], a["temp"],
                        a["top_k"], a["top_p"], a["rep"], a["keys"])
    np.testing.assert_array_equal(tok, logits.argmax(-1))
    ref_lp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    np.testing.assert_allclose(
        lp, np.asarray(ref_lp)[np.arange(4), tok], rtol=1e-5)


def test_top_k_restricts_support():
    logits = np.array([[0.0, 3.0, 2.0, 1.0, -1.0, 0.5, 0.2, 0.1]],
                      np.float32)
    s = Sampler()
    seen = set()
    for draw in range(50):
        a = _arrays(1, temp=np.ones((1,), np.float32),
                    top_k=np.full((1,), 2, np.int32),
                    keys=sampling.request_key(0, 0, draw)[None])
        tok, _ = s(jnp.asarray(logits), a["presence"], a["temp"],
                   a["top_k"], a["top_p"], a["rep"], a["keys"])
        seen.add(int(tok[0]))
    assert seen <= {1, 2} and len(seen) == 2   # both top-2, nothing else


def test_top_p_collapses_to_nucleus():
    # token 0 holds ~88% of the mass: top_p=0.5 keeps only it, whatever
    # the temperature says
    logits = np.array([[4.0, 2.0, 1.0, 0.0, -1.0, -1.0, -1.0, -1.0]],
                      np.float32)
    s = Sampler()
    for draw in range(20):
        a = _arrays(1, temp=np.ones((1,), np.float32),
                    top_p=np.full((1,), 0.5, np.float32),
                    keys=sampling.request_key(0, 0, draw)[None])
        tok, _ = s(jnp.asarray(logits), a["presence"], a["temp"],
                   a["top_k"], a["top_p"], a["rep"], a["keys"])
        assert int(tok[0]) == 0


def test_repetition_penalty_flips_argmax():
    logits = np.array([[2.0, 1.9] + [-5.0] * 6], np.float32)
    presence = np.zeros((1, 8), bool)
    presence[0, 0] = True                      # token 0 already emitted
    a = _arrays(1, presence=presence, rep=np.full((1,), 5.0, np.float32))
    tok, _ = Sampler()(jnp.asarray(logits), a["presence"], a["temp"],
                       a["top_k"], a["top_p"], a["rep"], a["keys"])
    assert int(tok[0]) == 1                    # penalized off the argmax
    # penalty 1.0 is a no-op even with presence set
    a = _arrays(1, presence=presence)
    tok, _ = Sampler()(jnp.asarray(logits), a["presence"], a["temp"],
                       a["top_k"], a["top_p"], a["rep"], a["keys"])
    assert int(tok[0]) == 0


def test_sample_row_independent_of_batch_composition():
    """A row's draw depends only on (its logits, its key) — per-request
    reproducibility whatever else shares the batch."""
    rng = np.random.default_rng(3)
    row = rng.normal(size=(8,)).astype(np.float32)
    s = Sampler()
    outs = []
    for other in (0.0, 99.0):
        logits = np.stack([row, np.full((8,), other, np.float32)])
        a = _arrays(2, temp=np.ones((2,), np.float32))
        tok, _ = s(jnp.asarray(logits), a["presence"], a["temp"],
                   a["top_k"], a["top_p"], a["rep"], a["keys"])
        outs.append(int(tok[0]))
    assert outs[0] == outs[1]


def test_sample_np_mirrors_greedy_and_filters():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(8,))
    tok, lp = sampling.sample_np(logits, SamplingParams(), rng)
    assert tok == int(np.argmax(logits)) and np.isfinite(lp)
    for _ in range(20):
        tok, _ = sampling.sample_np(
            logits, SamplingParams(temperature=1.0, top_k=2), rng)
        assert tok in set(np.argsort(logits)[-2:])


def test_stop_truncate_matcher():
    assert sampling.stop_truncate([1, 2, 3], ((2, 3),)) == 1
    assert sampling.stop_truncate([1, 2, 3], ((9,), (3,))) == 2
    assert sampling.stop_truncate([1, 2, 3], ((1, 2, 3),)) == 0
    assert sampling.stop_truncate([1, 2, 3], ((2, 2),)) is None
    assert sampling.stop_truncate([1], ((1, 1),)) is None


# ---------------------------------------------------------------------------
# end-to-end through the engine / streaming API


def test_explicit_greedy_params_match_default(nectar):
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 21])
    base, _ = _serve(cfg, params, prompts, **_kw())
    sp = SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                        repetition_penalty=1.0)
    expl, _ = _serve(cfg, params, prompts, sampling_params=sp, **_kw())
    assert base == expl


def test_stop_sequence_truncates_stream(nectar):
    cfg, _, params = nectar
    prompt = _prompts(cfg, [9], seed=2)[0]
    base, _ = _serve(cfg, params, [prompt], max_new=12, **_kw())
    toks = base[0]
    stop = tuple(toks[3:5])                    # will be hit mid-stream
    cut = None
    for i in range(len(toks)):
        cut = sampling.stop_truncate(toks[:i + 1], (stop,))
        if cut is not None:
            break
    assert cut is not None
    got, eng = _serve(cfg, params, [prompt], max_new=12,
                      sampling_params=SamplingParams(stop=(stop,)), **_kw())
    assert got[0] == toks[:cut]                # match excluded
    assert eng._requests[0].done
    assert eng.pool.n_free == eng.pool.n_blocks
    # legacy slot path shares the matcher
    legacy, _ = _serve(cfg, params, [prompt], max_new=12,
                       sampling_params=SamplingParams(stop=(stop,)),
                       max_batch=2, max_seq=64, paged=False)
    assert legacy[0] == toks[:cut]


def test_max_tokens_caps_and_rolls_back_spec(nectar):
    """sampling.max_tokens tightens max_new; under speculation the
    over-drafted tail rolls back through PagedKVCache.truncate and every
    block returns to the pool."""
    cfg, _, params = nectar
    pat = np.tile(np.array([3, 1, 4, 1, 5], np.int32), 6)
    sp = SamplingParams(max_tokens=5)
    got, eng = _serve(cfg, params, [pat], max_new=16, sampling_params=sp,
                      spec=SpecConfig(drafter="ngram", k=4, k_max=6),
                      **_kw(max_seq=96))
    assert len(got[0]) == 5
    assert eng.pool.n_free == eng.pool.n_blocks
    base, _ = _serve(cfg, params, [pat], max_new=5, **_kw(max_seq=96))
    assert got[0] == base[0]                   # greedy identity at the cap


def test_temperature_stream_reproducible_and_plumbed(nectar):
    """Temperature + top-k sampling end-to-end through the streaming API:
    same SamplingParams.seed -> same stream; temperature actually changes
    the output vs greedy (the seed engine's hard-coded-greedy bug)."""
    cfg, _, params = nectar
    prompt = _prompts(cfg, [9], seed=5)[0]
    sp = SamplingParams(temperature=0.9, top_k=8, seed=11)

    def stream():
        eng = Engine(cfg, params, ServeConfig(**_kw()))
        srv = api.StreamingServer(eng)
        rid = srv.submit(prompt, max_new=10, sampling=sp)
        srv.drain()
        return [int(t) for t in srv.result(rid).tokens_out]

    s1, s2 = stream(), stream()
    assert s1 == s2                            # per-request seed contract
    greedy, _ = _serve(cfg, params, [prompt], max_new=10, **_kw())
    assert s1 != greedy[0]


def test_logprobs_threaded(nectar):
    cfg, _, params = nectar
    prompt = _prompts(cfg, [7], seed=6)[0]
    eng = Engine(cfg, params, ServeConfig(**_kw()))
    srv = api.StreamingServer(eng)
    rid = srv.submit(prompt, max_new=6,
                     sampling=SamplingParams(logprobs=True))
    srv.drain()
    req = srv.result(rid)
    assert len(req.logprobs_out) == len(req.tokens_out) == 6
    assert all(np.isfinite(lp) and lp <= 0.0 for lp in req.logprobs_out)


def test_flash_backend_token_identical(nectar):
    """The Pallas paged flash-decode backend serves the same tokens as
    the naive gather (ROADMAP item: kernels read block tables directly)."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [5, 21], seed=7)
    naive, _ = _serve(cfg, params, prompts, **_kw())
    flash, _ = _serve(cfg, params, prompts, attn_backend="flash", **_kw())
    assert naive == flash


def test_flash_backend_covers_verify_and_prefill_rows(nectar):
    """ROADMAP item: the paged Pallas kernel extends to S > 1 query rows,
    so attn_backend='flash' also serves speculative K+1 verify rows and
    chunked-prefill rows of the unified step — token-identical to the
    naive gather under speculation (which exercises every width)."""
    cfg, _, params = nectar
    spec = SpecConfig(drafter="ngram", k=3, k_max=4, adaptive=False)
    prompts = _prompts(cfg, [6, 19], seed=11)
    naive, _ = _serve(cfg, params, prompts, max_new=12,
                      **_kw(spec=spec))
    flash, eng = _serve(cfg, params, prompts, max_new=12,
                        **_kw(spec=spec, attn_backend="flash"))
    assert naive == flash
    assert eng.metrics.spec_steps > 0       # verify rows actually ran


# ---------------------------------------------------------------------------
# prompt logprobs (ROADMAP item: runner already emits all-position logits)


def test_prompt_logprobs_match_full_forward(nectar):
    """prompt_logprobs_out[i] == log softmax(logits[i-1])[prompt[i]] from
    a whole-prompt forward; index 0 is None. The prompt spans several
    prefill chunks, so the chunk-seam stitching is exercised."""
    cfg, model, params = nectar
    prompt = _prompts(cfg, [37], seed=12)[0]
    eng = Engine(cfg, params, ServeConfig(**_kw(max_seq=96)))
    done = eng.run([Request(rid=0, prompt=prompt, max_new=2,
                            sampling=SamplingParams(prompt_logprobs=True))],
                   max_steps=200)
    plp = done[0].prompt_logprobs_out
    assert len(plp) == len(prompt) and plp[0] is None
    logits, _ = model.forward(params, {"tokens": jnp.asarray(prompt)[None]})
    z = np.asarray(logits)[0]
    for i in range(1, len(prompt)):
        ref = sampling.token_logprob(z[i - 1], int(prompt[i]))
        assert plp[i] == pytest.approx(ref, abs=2e-4)


def test_prompt_logprobs_survive_preemption(nectar):
    """Mid-prefill eviction clears the partial list; replay recomputes it
    — the final list must still match the clean run exactly."""
    cfg, _, params = nectar
    prompts = _prompts(cfg, [4, 20], seed=13)
    sp = SamplingParams(prompt_logprobs=True)

    def run(n_kv_blocks):
        eng = Engine(cfg, params, ServeConfig(
            **_kw(block_size=4, prefill_chunk=8, max_seq=64,
                  n_kv_blocks=n_kv_blocks)))
        reqs = [Request(rid=i, prompt=p, max_new=12, sampling=sp)
                for i, p in enumerate(prompts)]
        eng.run(reqs, max_steps=1000)
        return [list(r.prompt_logprobs_out) for r in reqs], eng

    free, _ = run(0)
    tight, eng = run(10)
    assert eng.metrics.evictions > 0
    assert free == tight


def test_prompt_logprobs_rejected_on_legacy_engine(nectar):
    cfg, _, params = nectar
    eng = Engine(cfg, params, ServeConfig(paged=False))
    with pytest.raises(ValueError, match="prompt_logprobs"):
        eng.add_request(Request(
            rid=0, prompt=np.arange(4, dtype=np.int32),
            sampling=SamplingParams(prompt_logprobs=True)))


def test_flash_backend_rejects_int8_kv(nectar):
    cfg, _, params = nectar
    with pytest.raises(ValueError, match="flash"):
        Engine(cfg, params, ServeConfig(**_kw(attn_backend="flash",
                                              kv_quant=True)))
    with pytest.raises(ValueError, match="attn_backend"):
        Engine(cfg, params, ServeConfig(**_kw(attn_backend="nope")))


# ---------------------------------------------------------------------------
# unified runner: one step, mixed phases


def test_runner_mixed_prefill_decode_batch(nectar):
    """One ModelRunner.step with a PREFILL row and a DECODE row in the
    same batch reproduces the single-phase results row-for-row."""
    cfg, model, params = nectar
    scfg = ServeConfig(**_kw())
    P = 11
    prompt = _prompts(cfg, [P], seed=8)[0]

    def prefill_into(runner, slot, tables):
        b = runner.new_batch(P, tables)
        b.add_row(slot, PREFILL, prompt, 0)
        return runner.step(b)

    # solo: prefill row alone, then decode row alone
    r1 = ModelRunner(model, params, scfg)
    tables = np.full((scfg.max_batch, scfg.blocks_per_seq),
                     scfg.pool_blocks, np.int32)
    tables[0, :2] = [0, 1]
    out_p = prefill_into(r1, 0, tables)
    first = int(np.asarray(out_p.last_logits)[0].argmax())
    b = r1.new_batch(1, tables)
    b.add_row(0, DECODE, [first], P)
    second = int(np.asarray(r1.step(b).last_logits)[0].argmax())

    # mixed: row 1 prefills WHILE row 0 decodes, in one call
    r2 = ModelRunner(model, params, scfg)
    tables2 = np.full_like(tables, scfg.pool_blocks)
    tables2[0, :2] = [0, 1]
    tables2[1, :2] = [2, 3]
    prefill_into(r2, 0, tables2)
    b = r2.new_batch(P, tables2)
    b.add_row(0, DECODE, [first], P)
    b.add_row(1, PREFILL, prompt, 0)
    out = r2.step(b)
    last = np.asarray(out.last_logits)
    assert int(last[0].argmax()) == second         # decode row unchanged
    assert int(last[1].argmax()) == first          # prefill row unchanged
    assert out.row_logits(1).shape[0] == b.tokens.shape[1]


def test_runner_width_buckets(nectar):
    """The generation group runs at width 1 or k_max+1 and the prefill
    group at the prefill buckets; the token buffer widens to the chunk
    only when prefill rows may ride along."""
    cfg, model, params = nectar
    scfg = ServeConfig(**_kw(spec=SpecConfig(k_max=6)))
    r = ModelRunner(model, params, scfg)
    tables = np.zeros((2, scfg.blocks_per_seq), np.int32)
    assert r.widths == [1, 7]
    assert r.prefill_buckets == [1, 2]
    assert [r.prefill_bucket_for(n) for n in (0, 1, 2)] == [0, 1, 2]
    shapes = {n: r.new_batch(n, tables) for n in (1, 5, 7)}
    assert {n: (b.width, b.tokens.shape[1], b.prefill_bucket)
            for n, b in shapes.items()} == {1: (1, 1, 0), 5: (7, 7, 0),
                                            7: (7, 7, 0)}
    b = r.new_batch(5, tables, n_prefill=2)
    assert (b.width, b.tokens.shape[1], b.prefill_bucket) == (7, 16, 2)
    # a row longer than every generation width can only be a prefill row
    b = r.new_batch(9, tables)
    assert (b.width, b.tokens.shape[1], b.prefill_bucket) == (7, 16, 0)
    b.add_row(0, PREFILL, np.arange(9), 0)
    assert b.prefill_bucket == 1


# ---------------------------------------------------------------------------
# batched drafting


def test_streaming_never_emits_retracted_stop_prefix(nectar):
    """Regression: a partial stop-sequence match is held back from the
    stream until resolved — a token already sent to a client cannot be
    unsent when the match completes a tick later."""
    cfg, _, params = nectar
    prompt = _prompts(cfg, [9], seed=2)[0]
    base, _ = _serve(cfg, params, [prompt], max_new=12, **_kw())
    toks = base[0]
    stop = tuple(toks[3:5])                    # completes across 2 ticks
    eng = Engine(cfg, params, ServeConfig(**_kw()))
    srv = api.StreamingServer(eng)
    rid = srv.submit(prompt, max_new=12,
                     sampling=SamplingParams(stop=(stop,)))
    streamed = []
    for _ in range(200):
        streamed.extend(srv.poll().get(rid, []))
        if srv.result(rid) is not None:
            break
    final = [int(t) for t in srv.result(rid).tokens_out]
    assert [int(t) for t in streamed] == final   # nothing retracted
    # partial-match holdback helper
    assert sampling.stop_holdback([1, 2, 7], ((7, 8, 9),)) == 1
    assert sampling.stop_holdback([1, 7, 8], ((7, 8, 9),)) == 2
    assert sampling.stop_holdback([1, 2, 3], ((7, 8),)) == 0


def test_explicit_greedy_survives_spec_temperature(nectar):
    """Regression: SpecConfig.temperature is the default for requests
    that DON'T choose (temperature=None); an explicit temperature=0.0
    stays greedy even on a temperature-sampling spec engine."""
    cfg, _, params = nectar
    pat = np.tile(np.array([3, 1, 4, 1, 5], np.int32), 6)
    base, _ = _serve(cfg, params, [pat], max_new=10, **_kw(max_seq=96))
    sp_kw = dict(spec=SpecConfig(drafter="ngram", k=3, k_max=4,
                                 temperature=0.8), max_seq=96)
    greedy, _ = _serve(cfg, params, [pat], max_new=10,
                       sampling_params=SamplingParams(temperature=0.0),
                       **_kw(**sp_kw))
    assert greedy == base                      # explicit greedy wins
    inherit, _ = _serve(cfg, params, [pat], max_new=10, **_kw(**sp_kw))
    assert inherit != base                     # unset inherits spec temp


def test_spec_acceptance_honors_sampling_filters(nectar):
    """Regression: top-k/top-p/repetition-penalty apply to the verify
    acceptance law too, not just the first token. top_k=1 makes the
    filtered target a point mass, so temperature sampling under spec
    must reproduce the greedy stream token-for-token — on the old
    unfiltered acceptance it drew from the full-vocab softmax."""
    cfg, _, params = nectar
    pat = np.tile(np.array([3, 1, 4, 1, 5], np.int32), 6)
    base, _ = _serve(cfg, params, [pat], max_new=12, **_kw(max_seq=96))
    sp = SamplingParams(temperature=0.9, top_k=1)
    spec, eng = _serve(cfg, params, [pat], max_new=12, sampling_params=sp,
                       spec=SpecConfig(drafter="ngram", k=3, k_max=4),
                       **_kw(max_seq=96))
    assert spec == base
    assert eng.metrics.summary()["spec_steps"] > 0
    # and the same point-mass request on the non-spec engine agrees
    plain, _ = _serve(cfg, params, [pat], max_new=12, sampling_params=sp,
                      **_kw(max_seq=96))
    assert plain == base


def test_drafter_eviction_never_drops_live_rows(nectar):
    """Regression: with draft slots full, a propose_batch mixing a cached
    rid and a new rid must evict only rids OUTSIDE the call (the old
    pick could evict a live row mid-call and KeyError)."""
    cfg, _, params = nectar
    dcfg = get_config("nectar-relu-llama-draft")
    dparams = Model(dcfg).init(jax.random.PRNGKey(7))
    ctxs = _prompts(cfg, [8, 8, 8], seed=10)
    d = ModelDrafter(dcfg, dparams, max_seq=64, max_batch=2)
    d.propose(1, ctxs[0], 2)
    d.propose(2, ctxs[1], 2)                   # slots now full: {1, 2}
    out = d.propose_batch([(1, ctxs[0], 2), (3, ctxs[2], 2)])
    assert len(out[0][0]) == 2 and len(out[1][0]) == 2
    assert 2 not in d._caches                  # the idle rid was evicted
    fresh = ModelDrafter(dcfg, dparams, max_seq=64, max_batch=2)
    assert list(out[1][0]) == list(fresh.propose(3, ctxs[2], 2)[0])


def test_batched_drafter_matches_sequential(nectar):
    """propose_batch over several requests equals per-request proposals
    from a fresh drafter (batching changes cost, never content) — and
    spends ONE batched step per draft token, not one per row."""
    cfg, _, params = nectar
    dcfg = get_config("nectar-relu-llama-draft")
    dparams = Model(dcfg).init(jax.random.PRNGKey(7))
    ctxs = _prompts(cfg, [9, 14], seed=9)

    batched = ModelDrafter(dcfg, dparams, max_seq=64, max_batch=2)
    out = batched.propose_batch([(0, ctxs[0], 3), (1, ctxs[1], 3)])
    steps_batched = batched.steps

    seq_out = []
    for rid, ctx in enumerate(ctxs):
        fresh = ModelDrafter(dcfg, dparams, max_seq=64, max_batch=2)
        seq_out.append(fresh.propose(rid, ctx, 3))
    for (t_b, _), (t_s, _) in zip(out, seq_out):
        assert list(t_b) == list(t_s)
    # catch-up is bounded by the LONGEST context, not the sum
    assert steps_batched <= max(len(c) for c in ctxs) + 3
