"""The latent-attention MoE family against its plain float32 reference
(``bench/reference/mla_moe.py``) at smoke size on the CPU: chunked
prefill and then decode through the paged latent pool, in uniform and in
grouped steps, give the reference's full-forward logits; the reference's
blocked attention equals its unblocked attention."""

import jax
import numpy as np
import pytest

from bench import harness

# Moonlight's shapes scaled down: one leading dense layer, then two MoE
# layers whose router spans 8 experts, of which this chip holds [2, 6).
CONF = {
    "name": "moonlight-smoke", "family": "mla_moe", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 4, "held_experts": [2, 6],
    "published": {"n_routed_experts": 8}, "num_experts_per_tok": 2,
    "n_shared_experts": 2, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 50000.0, "tie_word_embeddings": False,
    "torch_dtype": "float32",
}

C = 8                       # prefill chunk
PROMPTS = (30, 5, 12, 20)   # ticks carry 4, 3, 2, then 1 prefill rows
N_DECODE = 4


@pytest.fixture(scope="module")
def smoke():
    fam = harness.family(CONF)
    params = jax.jit(lambda k: fam.init_weights(CONF, k))(
        harness.seed_key(2 ** 33 + 15))
    return fam.model_config(CONF), params


def _reference_logits(params, seq):
    from bench.reference import dense_gqa, mla_moe
    x = mla_moe.forward_hidden(CONF, params, seq, quant=False)[:len(seq)]
    with jax.default_matmul_precision("highest"):
        h = dense_gqa._rms(x, params["final_norm"], CONF["rms_norm_eps"])
        return np.asarray(h @ params["head"])


@pytest.mark.parametrize("grouped", [False, True])
def test_paged_prefill_then_decode_match_reference(smoke, grouped):
    """Four sequences fed through the runner: prompts in chunks of C (as
    PREFILL rows), then N_DECODE tokens one per tick (DECODE rows),
    teacher-forced. Every position's logits equal the reference's full
    forward pass over the sequence (f32; rtol 1e-4 leaves room for the
    latent-space reassociation of q.k and p.v, which the reference
    computes expanded)."""
    from repro.configs.base import ServeConfig
    from repro.models import Model
    from repro.serve.runner import DECODE, PREFILL, ModelRunner
    cfg, params = smoke
    scfg = ServeConfig(max_batch=4, max_seq=64, paged=True, block_size=8,
                       prefill_chunk=C)
    runner = ModelRunner(Model(cfg), params, scfg)
    MB = scfg.blocks_per_seq
    tables = np.arange(4 * MB, dtype=np.int32).reshape(4, MB)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 256, n + N_DECODE, dtype=np.int32)
            for n in PROMPTS]
    got = [[] for _ in PROMPTS]
    pos = [0] * len(PROMPTS)
    buckets = set()
    while any(p < len(s) for p, s in zip(pos, seqs)):
        rows = []
        for slot, (p, s) in enumerate(zip(pos, seqs)):
            if p < PROMPTS[slot]:
                rows.append((slot, PREFILL, s[p:min(p + C, PROMPTS[slot])]))
            elif p < len(s):
                rows.append((slot, DECODE, s[p:p + 1]))
        n_pf = sum(1 for r in rows if r[1] == PREFILL)
        b = runner.new_batch(max(len(r[2]) for r in rows), tables,
                             n_prefill=n_pf if grouped else 0)
        buckets.add(b.prefill_bucket)
        for slot, phase, toks in rows:
            b.add_row(slot, phase, toks, pos[slot])
        out = runner.step(b)
        for slot, _, toks in rows:
            got[slot].append(out.row_logits(slot)[:len(toks)])
            pos[slot] += len(toks)
    assert buckets == ({0, 1, 4} if grouped else {0})
    for slot, seq in enumerate(seqs):
        mine = np.concatenate(got[slot])
        np.testing.assert_allclose(mine, _reference_logits(params, seq),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("L,block", [(16, 4), (64, 8), (64, 16)])
def test_reference_blocked_attention_equals_unblocked(L, block):
    from bench.reference import mla_moe
    ks = jax.random.split(jax.random.PRNGKey(L + block), 3)
    q = jax.random.normal(ks[0], (L, 4, 24))
    k = jax.random.normal(ks[1], (L, 4, 24))
    v = jax.random.normal(ks[2], (L, 4, 16))
    with jax.default_matmul_precision("highest"):
        blocked = mla_moe._attend(q, k, v, block)
        whole = mla_moe._attend(q, k, v, L)
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-6)


def test_reference_hidden_independent_of_query_block(smoke):
    """The whole reference forward gives the same hidden states whatever
    its query block (padding to the block's bucket included)."""
    from bench.reference import mla_moe
    _, params = smoke
    seq = np.random.default_rng(3).integers(0, 256, 40, dtype=np.int32)
    a = mla_moe.forward_hidden(CONF, params, seq, False, block=8)[:40]
    b = mla_moe.forward_hidden(CONF, params, seq, False, block=64)[:40]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_served_scores_pick_the_reference_argmax(smoke):
    """served_scores: a token that is the reference's argmax has gap 0,
    any other a positive gap; log-probabilities are negative; the float8
    control reads farther from the reference than the reference itself."""
    from bench.reference import mla_moe
    _, params = smoke
    prompt = np.random.default_rng(4).integers(0, 256, 9, dtype=np.int32)
    best = []
    for _ in range(5):         # the reference's own greedy continuation
        seq = np.concatenate([prompt, np.asarray(best, np.int32)])
        best.append(int(_reference_logits(params, seq)[-1].argmax()))
    best = np.asarray(best, np.int32)
    gap, lp = mla_moe.served_scores(CONF, params, prompt, best)
    assert np.all(gap == 0) and np.all(lp < 0)
    gap_c, lp_c = mla_moe.served_scores(CONF, params, prompt, best,
                                        control=True)
    assert np.abs(lp_c - lp).max() > 1e-3
    gap, _ = mla_moe.served_scores(CONF, params, prompt, (best + 1) % 256)
    assert np.all(gap > 0)


def _cell():
    """The smoke model under the smoke traffic, reporting the metrics
    BENCHMARK.json gives ``moonlight-16b-a3b.chat``."""
    from bench.tests import smoke
    cell = harness.load_cell("moonlight-16b-a3b.chat")
    spec = {k: [{n: v for n, v in m.items() if n != "workloads"}
                for m in cell.metrics(k)]
            for k in ("end_to_end", "per_layer")}
    return harness.Cell(name="moonlight-smoke.chat", chips=1, conf=CONF,
                        mix=smoke.MIX,
                        settings=dict(smoke.SETTINGS, rate_per_s=4.0),
                        spec=spec)


def test_traced_run_is_correct():
    """A whole traced run at smoke size on the CPU is correct against the
    reference. (A CPU trace has no device ops, so the roofline reader
    finds nothing there and the metric is left out, without raising.)"""
    from bench import run
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    out = run.execute(_cell(), 2 ** 31 + 21, 2.0, True, jax.devices(), peak)
    assert out["correct"] is True, out["check"]
    assert out["failed"] == 0 and "breakdown" in out


def test_roofline_reader_divides_least_time_by_device_time(monkeypatch,
                                                           capsys):
    """moe_step_roofline over a decode tick and a tick with a prefill
    row: the least time of each tick's needed work (bench/work_mla_moe.py)
    summed, over their summed device time; a tick with no device time is
    left out; the binding term goes to standard error."""
    from bench import readings, work, work_mla_moe
    conf = dict(CONF, n_routed_experts=2, held_experts=[0, 2])
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ticks = [(harness.Tick(0, 0.0, 1.0, [(1, 10, True)] * 3, 0, 0), 2e6),
             (harness.Tick(1, 1.0, 2.0, [(1, 11, True)] * 2
                           + [(8, 16, False)], 1, 0), 4e6),
             (harness.Tick(2, 2.0, 3.0, [(1, 12, True)], 0, 0), 0)]
    monkeypatch.setattr(readings, "traced",
                        lambda run: [(t, d, 1e9) for t, d in ticks])
    reader = harness.metric_reader("moe_step_roofline.chat")
    run = readings.Run(cell=harness.Cell("x", 1, conf, {}, {}, {}),
                       win=None, setup_s=0.0, peak=peak)
    least = sum(max(work.least_seconds(
        work_mla_moe.tick_work(conf, t.rows), peak)) for t, _ in ticks[:2])
    assert reader.read(run) == pytest.approx(100 * least / 6e-3)
    assert "memory binds" in capsys.readouterr().err
