"""FFN: dense GLU / non-GLU, with the NeCTAr sparse decode path.

Training/prefill always run the dense MXU path. At decode, configs with
``relu_sparse`` route through ``gathered_sparse_ffn`` (paper C2) and configs
with ``int8_weights`` use the quantized NMCE contract (paper C1); both are
validated against the dense path in tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import sparsity
from repro.dist.sharding import constrain_tp_exact
from repro.models import layers


def init_ffn(key, cfg: ModelConfig, dtype, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": layers.dense_init(ks[0], (d, f), dtype),
         "w_down": layers.dense_init(ks[1], (f, d), dtype)}
    if cfg.glu:
        p["w_gate"] = layers.dense_init(ks[2], (d, f), dtype)
    return p


def ffn_forward(p, cfg: ModelConfig, x):
    """Dense path (train/prefill)."""
    act = "relu" if cfg.relu_sparse else cfg.act
    return sparsity.dense_ffn(x, p["w_up"], p["w_down"], act=act,
                              w_gate=p.get("w_gate"))


def ffn_decode(p, cfg: ModelConfig, x):
    """Decode path: sparse gather when relu_sparse (the paper's technique),
    dense otherwise. x: [B, 1, d]."""
    if not cfg.relu_sparse:
        return ffn_forward(p, cfg, x)
    k = sparsity.active_fraction_to_k(cfg.d_ff, cfg.sparse_k_frac)
    return sparsity.gathered_sparse_ffn(
        x, p["w_up"], p["w_down"], k=k, act="relu", w_gate=p.get("w_gate"))


def ffn_step(p, cfg: ModelConfig, x, n_gen: int | None = None):
    """The FFN of the serving step (ModelRunner), split by row group:
    generation tokens (decode and verify rows) take the sparse decode
    path — verify must score each position with EXACTLY the decode-step
    math or greedy spec output would drift from the non-speculative
    engine — and prefill tokens the dense one.

    ``n_gen`` (static) is None when x holds generation tokens only (no
    prefill group): the step then compiles to the pure sparse decode
    path and never streams the dense W_down, which is the weight traffic
    the paper's sparsity exists to avoid. Otherwise x is [1, N, d], the
    generation group's n_gen tokens first and the prefill group's after
    them: both parts share the up/gate hidden activation and each runs
    only its own down-projection, each exactly ``gathered_sparse_ffn`` /
    ``dense_ffn``, which keeps a step with both groups token-identical to
    ticks run phase by phase.
    """
    # bit-reproducible layout (exact_tp, identity off-scope): the hidden
    # activation all-gathers before the down-projection so the contraction
    # runs over a replicated d_ff against the output-sharded w_down — a
    # concatenation instead of a psum of partials (the sparse gather path
    # is psum-free already; the dense branch is not without this)
    #
    # named_scope: the profiling contract (obs.costmodel attributes HLO
    # op cost by scope name). "ffn_dense" covers the dense MXU path plus
    # the two groups' shared up/gate hidden; "ffn_sparse" the gathered
    # decode path. Metadata only — no math change.
    if not cfg.relu_sparse:
        with jax.named_scope("ffn_dense"):
            return constrain_tp_exact(ffn_forward(p, cfg, x))
    if n_gen is None:
        with jax.named_scope("ffn_sparse"):
            return constrain_tp_exact(ffn_decode(p, cfg, x))
    with jax.named_scope("ffn_dense"):
        h = sparsity.ffn_hidden(x, p["w_up"], "relu", p.get("w_gate"))
        h = constrain_tp_exact(h)
        down_d = sparsity.down_dense(h[:, n_gen:], p["w_down"])
    k = sparsity.active_fraction_to_k(cfg.d_ff, cfg.sparse_k_frac)
    with jax.named_scope("ffn_sparse"):
        down_s = sparsity.down_sparse(h[:, :n_gen], p["w_down"], k)
    return constrain_tp_exact(jnp.concatenate([down_s, down_d], axis=1))
