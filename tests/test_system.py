"""System behaviour tests.

Per assigned architecture: a REDUCED config of the same family runs one
forward + one train step on CPU (shapes + no NaNs). Plus end-to-end
behaviour: training the paper's 1.7M ReLU-Llama reduces loss and develops
activation sparsity; heterogeneous dispatch routes decode to the NMCE path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs
from repro.configs.base import SHAPES, TrainConfig, applicable_shapes
from repro.core import heterogeneous, sparsity
from repro.models import Model
from repro.train import data
from repro.train.loop import run_training

ARCHS = [
    "llama3.2-1b", "granite-34b", "qwen3-4b", "qwen2.5-3b",
    "llama4-maverick-400b-a17b", "moonshot-v1-16b-a3b", "qwen2-vl-72b",
    "zamba2-2.7b", "musicgen-medium", "xlstm-125m",
]

SMOKE_OF = {
    "llama3.2-1b": "llama3.2-1b-smoke",
    "granite-34b": "granite-34b-smoke",
    "qwen3-4b": "qwen3-4b-smoke",
    "qwen2.5-3b": "qwen2.5-3b-smoke",
    "llama4-maverick-400b-a17b": "llama4-maverick-smoke",
    "moonshot-v1-16b-a3b": "moonshot-v1-smoke",
    "qwen2-vl-72b": "qwen2-vl-smoke",
    "zamba2-2.7b": "zamba2-smoke",
    "musicgen-medium": "musicgen-smoke",
    "xlstm-125m": "xlstm-smoke",
}


def make_batch(cfg, B=2, S=16, key=jax.random.PRNGKey(0)):
    ks = jax.random.split(key, 4)
    shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks else (B, S)
    batch = {
        "tokens": jax.random.randint(ks[0], shape, 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], shape, 0, cfg.vocab),
    }
    if cfg.frontend == "vision_stub":
        batch["vision_embeds"] = jax.random.normal(
            ks[2], (B, S // 2, cfg.d_model), jnp.float32)
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None, :], (3, B, S))
    if cfg.frontend == "audio_stub":
        batch["audio_embeds"] = jax.random.normal(
            ks[2], (B, S // 2, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    """Deliverable (f): reduced-config smoke per assigned architecture."""
    cfg = get_config(SMOKE_OF[arch])
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = make_batch(cfg)

    logits, aux = model.forward(params, batch)
    expect = (2, 16, cfg.n_codebooks, cfg.vocab) if cfg.n_codebooks \
        else (2, 16, cfg.vocab)
    assert logits.shape == expect, (arch, logits.shape)
    assert bool(jnp.all(jnp.isfinite(logits))), arch

    (loss, metrics), grads = jax.value_and_grad(
        model.loss, has_aux=True)(params, batch)
    assert bool(jnp.isfinite(loss)), (arch, loss)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(gnorm)), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_decode_step(arch):
    cfg = get_config(SMOKE_OF[arch])
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    cache = model.init_cache(2, 24, jnp.float32)
    batch = make_batch(cfg, S=8)
    _, cache = model.prefill(params, {"tokens": batch["tokens"]}, cache)
    tok = jnp.zeros((2, 1, cfg.n_codebooks) if cfg.n_codebooks else (2, 1),
                    jnp.int32)
    logits, cache = model.decode_step(params, tok, cache)
    assert bool(jnp.all(jnp.isfinite(logits))), arch
    assert int(cache["lens"][0]) == 9


def test_full_configs_match_assignment():
    """The exact assigned hyperparameters are encoded."""
    spec = {
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "moonshot-v1-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
        "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
    }
    for name, (L, d, H, kv, ff, V) in spec.items():
        cfg = get_config(name)
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.d_ff, cfg.vocab)
        assert got == (L, d, H, kv, ff, V), (name, got)
    # MoE / family extras
    l4 = get_config("llama4-maverick-400b-a17b")
    assert (l4.n_experts, l4.top_k) == (128, 1)
    ms = get_config("moonshot-v1-16b-a3b")
    assert (ms.n_experts, ms.top_k) == (64, 6)
    assert (ms.kv_lora_rank, ms.qk_nope_head_dim, ms.qk_rope_head_dim,
            ms.v_head_dim) == (512, 128, 64, 128)
    assert (ms.first_k_dense_replace, ms.dense_d_ff) == (1, 11264)
    assert (ms.scoring_func, ms.router_bias, ms.routed_scaling_factor) \
        == ("sigmoid", True, 2.446)
    assert get_config("zamba2-2.7b").ssm_state == 64
    assert get_config("qwen3-4b").qk_norm
    assert get_config("qwen2.5-3b").qkv_bias
    assert get_config("qwen2-vl-72b").mrope
    assert get_config("musicgen-medium").n_codebooks == 4


def test_long_500k_applicability():
    """long_500k only for sub-quadratic archs (DESIGN.md §5)."""
    runs_long = {a for a in ARCHS
                 if "long_500k" in applicable_shapes(get_config(a))}
    assert runs_long == {"zamba2-2.7b", "xlstm-125m"}
    assert SHAPES["long_500k"]["seq"] == 524288


def test_e2e_train_reduces_loss_and_develops_sparsity():
    """The paper's workload: 1.7M ReLU-Llama on (synthetic) TinyStories."""
    cfg = get_config("nectar-relu-llama-1.7m")
    model = Model(cfg)
    src = data.TinyStoriesSynth(data.DataConfig(
        seq_len=64, batch_size=8, vocab_size=cfg.vocab))
    tcfg = TrainConfig(lr=3e-3, warmup_steps=5, total_steps=60, seed=0)
    params, _, info = run_training(model, cfg, tcfg, src, steps=60,
                                   log_every=1)
    losses = [m["ce"] for _, m in info["history"]]
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])

    # activation sparsity after ReLU (paper [11]: high for ReLU nets)
    batch = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    p0 = jax.tree.map(lambda a: a[0], params["units"]["b0"])
    from repro.models import layers as L
    h = L.rms_norm(x, p0["norm2"], cfg.norm_eps)
    hidden = jax.nn.relu(h @ p0["ffn"]["w_up"])
    frac = float(sparsity.sparsity_fraction(hidden))
    # ~50% at init (symmetric ReLU); grows toward ~90% with real training
    # ([11]) — the 60-step CPU probe just confirms the sparse regime exists;
    # bench_e2e tracks the growth curve over longer training.
    assert frac > 0.45, frac


def test_heterogeneous_dispatch_routes_decode_to_nmce():
    cfg = get_config("llama3.2-1b")
    rep = heterogeneous.decode_regime_report(cfg.d_model, cfg.d_ff,
                                             cfg.vocab, batch=8)
    assert rep["ffn_up"] == "gemv_stream"          # memory-bound -> NMCE
    assert rep["ffn_down_sparse"] == "sparse_gather"
    # prefill-sized matmul goes to the MXU
    site = heterogeneous.MatmulSite(rows=32 * 4096, k=2048, n=8192)
    assert heterogeneous.classify(site) == "gemm_mxu"


def test_registry_lists_all():
    names = list_configs()
    for a in ARCHS:
        assert a in names
    assert "nectar-relu-llama-1.7m" in names
