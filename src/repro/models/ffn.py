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


def ffn_step(p, cfg: ModelConfig, x, is_prefill, has_prefill: bool = True,
             n_decode: int | None = None):
    """Per-row FFN select for the unified batched step (ModelRunner):
    prefill rows take the dense path, decode/verify rows take the sparse
    decode path — in the SAME batch. x: [B, S, d]; is_prefill: bool[B].

    ``has_prefill`` is STATIC (the runner keys its jit on it): ticks with
    no prefill row — the serving steady state — compile to the pure
    sparse decode path and never touch the dense W_down stream, which is
    the weight traffic the paper's sparsity exists to avoid. Mixed ticks
    compute both branches from one shared hidden activation ``h`` (the
    up/gate matmuls are common), so the select costs one extra
    down-projection, not a second full FFN; each branch's expression is
    exactly ``dense_ffn`` / ``gathered_sparse_ffn``, which is what keeps
    unified-step output token-identical to the split per-phase engines.

    ``n_decode`` (static) is the grouped step's split in place of the
    per-row select: x is [1, N, d] with the decode group's n_decode
    tokens first and the prefill group's after them; each part runs only
    its own down-projection.
    """
    # bit-reproducible layout (exact_tp, identity off-scope): the hidden
    # activation all-gathers before the down-projection so the contraction
    # runs over a replicated d_ff against the output-sharded w_down — a
    # concatenation instead of a psum of partials (the sparse gather path
    # is psum-free already; the dense branch is not without this)
    #
    # named_scope: the profiling contract (obs.costmodel attributes HLO
    # op cost by scope name). "ffn_dense" covers the dense MXU path plus
    # the mixed-tick shared up/gate hidden; "ffn_sparse" the gathered
    # decode path. Metadata only — no math change.
    if not cfg.relu_sparse:
        with jax.named_scope("ffn_dense"):
            return constrain_tp_exact(ffn_forward(p, cfg, x))
    if not has_prefill:
        with jax.named_scope("ffn_sparse"):
            return constrain_tp_exact(ffn_decode(p, cfg, x))
    with jax.named_scope("ffn_dense"):
        h = sparsity.ffn_hidden(x, p["w_up"], "relu", p.get("w_gate"))
        h = constrain_tp_exact(h)
        h_d = h if n_decode is None else h[:, n_decode:]
        down_d = sparsity.down_dense(h_d, p["w_down"])
    k = sparsity.active_fraction_to_k(cfg.d_ff, cfg.sparse_k_frac)
    with jax.named_scope("ffn_sparse"):
        h_s = h if n_decode is None else h[:, :n_decode]
        down_s = sparsity.down_sparse(h_s, p["w_down"], k)
    if n_decode is not None:
        return constrain_tp_exact(
            jnp.concatenate([down_s, down_d], axis=1))
    return constrain_tp_exact(
        jnp.where(is_prefill[:, None, None], down_d, down_s))
