"""The serving MoE dispatch (models.moe.moe_serve) and the engine's MoE
routing counters, on the moonshot-v1-smoke config (latent attention, one
leading dense layer, sigmoid routing with a selection bias) in float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import AsyncConfig, ObsConfig, ServeConfig
from repro.core import sparsity as sp
from repro.models import Model, moe
from repro.serve.engine import Engine
from repro.serve.scheduler import Request


@pytest.fixture(scope="module")
def layer():
    cfg = get_config("moonshot-v1-smoke")
    p = moe.init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    p["router_bias"] = jax.random.uniform(jax.random.PRNGKey(4),
                                          (cfg.n_experts,), minval=-0.05,
                                          maxval=0.05)
    return cfg, p


def _hp(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


@pytest.mark.parametrize("others,padding", [(0, 5), (7, 0), (7, 5)])
def test_token_output_independent_of_its_tick(layer, others, padding):
    """A token's MoE output is the same alone and beside other tokens or
    padding rows (drop-free dispatch); padding is counted nowhere."""
    cfg, p = layer
    ks = jax.random.split(jax.random.PRNGKey(others + 10 * padding), 3)
    tok = jax.random.normal(ks[0], (1, cfg.d_model))
    alone, st_alone = _hp(moe.moe_serve, p, cfg, tok, jnp.ones((1,), bool))
    x = jnp.concatenate([jax.random.normal(ks[1], (others, cfg.d_model)),
                         tok,
                         jax.random.normal(ks[2], (padding, cfg.d_model))])
    valid = jnp.arange(x.shape[0]) <= others
    y, st = _hp(moe.moe_serve, p, cfg, x, valid)
    # f32 matmuls of another row count may round differently (1e-6 of
    # these values); a dropped or shifted expert would move them by O(1)
    np.testing.assert_allclose(y[others], alone[0], rtol=1e-5, atol=1e-5)
    assert int(st[0] + st[1]) == (others + 1) * cfg.top_k
    assert int(st_alone[0]) == cfg.top_k       # every expert held here


def test_held_shares_add_up_to_the_uncut_layer(layer):
    """Four chips' shares of the experts, each routed over all of them:
    their outputs, with the shared expert every chip computes counted
    once, add up to the layer with every expert held."""
    cfg, p = layer
    x = jax.random.normal(jax.random.PRNGKey(5), (12, cfg.d_model))
    valid = jnp.ones((12,), bool)
    whole, st_whole = _hp(moe.moe_serve, p, cfg, x, valid)
    n = cfg.n_experts // 4
    total, held = 0.0, 0
    for s in range(4):
        c = dataclasses.replace(cfg, held_experts=(s * n, (s + 1) * n))
        ps = dict(p, **{k: p[k][s * n:(s + 1) * n]
                        for k in ("w_up", "w_gate", "w_down")})
        y, st = _hp(moe.moe_serve, ps, c, x, valid)
        total = total + y
        held += int(st[0])
        assert int(st[0] + st[1]) == 12 * cfg.top_k
    sh = p["shared"]
    shared = _hp(sp.dense_ffn, x, sh["w_up"], sh["w_down"], cfg.act,
                 sh["w_gate"])
    np.testing.assert_allclose(total - 3 * shared, whole, rtol=1e-5,
                               atol=1e-5)
    assert held == int(st_whole[0]) == 12 * cfg.top_k


def test_router_sigmoid_bias_selection_and_weights(layer):
    """Selection by score plus bias, weights from the unbiased scores
    renormalised over the top-k and scaled."""
    cfg, p = layer
    x = jax.random.normal(jax.random.PRNGKey(6), (9, cfg.d_model))
    idx, w = moe.route_serve(p, cfg, x)
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    sel = s + np.asarray(p["router_bias"])
    want = np.argsort(-sel, axis=-1)[:, :cfg.top_k]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want, -1)).all()
    top = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, cfg.routed_scaling_factor * top / top.sum(-1, keepdims=True),
        rtol=1e-5)


def _engine(held=(0, 0), traced=False, async_cfg=None):
    cfg = dataclasses.replace(get_config("moonshot-v1-smoke"),
                              held_experts=held)
    params = Model(cfg).init(jax.random.PRNGKey(7))
    obs = ObsConfig(enabled=True, fence_device=False) if traced \
        else ObsConfig()
    scfg = ServeConfig(paged=True, max_batch=4, max_seq=64, block_size=8,
                       prefill_chunk=8, obs=obs, async_cfg=async_cfg)
    return cfg, Engine(cfg, params, scfg)


@pytest.mark.parametrize("held,async_cfg", [
    ((0, 0), None), ((2, 6), None),
    ((2, 6), AsyncConfig(enabled=True, max_device_ticks=1)),
    ((2, 6), AsyncConfig(enabled=True, max_device_ticks=4))],
    ids=["all-held", "held-2-6", "held-2-6-overlap", "held-2-6-burst"])
def test_counters_count_valid_tokens_only(held, async_cfg):
    """Prompts of 3-21 tokens (padded to the chunk in prefill rows,
    beside idle rows): the counters count top_k assignments per fed
    token and MoE layer, and no padding; held and elsewhere split by the
    held range; the tick attribute adds up to the held counter. Under
    the async engine too: its overlap ticks and its device-resident
    decode bursts (every step of a burst) are counted."""
    cfg, eng = _engine(held, traced=True, async_cfg=async_cfg)
    lens, max_new = [3, 21, 9, 14, 5], 4
    rng = np.random.default_rng(0)
    reqs = [Request(rid=eng.new_rid(),
                    prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
                    max_new=max_new) for n in lens]
    eng.run(reqs, max_steps=200)
    assert all(r.done for r in reqs)
    if async_cfg is not None:
        st = eng.async_stats()
        assert st["loop_bursts" if async_cfg.max_device_ticks > 1
                  else "overlap_ticks"] > 0
    c = eng.metrics.registry.labeled_counter(
        "engine_moe_assignments_total", "held").values
    ticks = eng.tracer.tick_stats
    assert sum(t.get("moe_held_assignments", 0) for t in ticks) \
        == c.get("true", 0)
    # the last overlap tick is reconciled, and its counts fetched, by the
    # next tick or a flush
    eng.flush_async()
    fed = sum(n + max_new - 1 for n in lens)
    if async_cfg is not None and async_cfg.max_device_ticks == 1:
        # an overlap tick is dispatched before the last tick's tokens
        # are known, so a row that finished there is fed once more (its
        # token discarded): count the tokens the ticks fed
        assert sum(t["valid_tokens"] for t in ticks) >= fed
        fed = sum(t["valid_tokens"] for t in ticks)
    n_moe = cfg.layer_kinds().count("moe")
    assert c.get("true", 0) + c.get("false", 0) == fed * cfg.top_k * n_moe
    if held == (0, 0):
        assert c.get("false", 0) == 0
    else:
        assert c["true"] > 0 and c["false"] > 0
    load = eng.metrics.registry.gauge("engine_moe_max_expert_load").value
    assert 1 <= load <= 4


def test_engine_grouped_and_uniform_ticks_agree():
    """Greedy tokens through the engine's grouped mixed ticks equal the
    per-request serving of the same prompts one at a time (no neighbour
    shares a tick)."""
    cfg, eng = _engine()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (17, 4, 11, 26)]
    together = [Request(rid=eng.new_rid(), prompt=p, max_new=6)
                for p in prompts]
    eng.run(together, max_steps=200)
    mixed = eng.metrics.registry.labeled_counter(
        "engine_mixed_ticks_total", "prefill_bucket").values
    assert mixed
    for p, r in zip(prompts, together):
        alone = Request(rid=eng.new_rid(), prompt=p, max_new=6)
        eng.run([alone], max_steps=200)
        assert alone.tokens_out == r.tokens_out
