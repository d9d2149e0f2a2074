"""Mixture-of-Experts with capacity-based gather dispatch (GShard-style).

Dispatch is sort-free and jit-static: each (token, choice) assignment gets a
slot inside its expert via a stable rank computation; overflowing tokens are
dropped (capacity_factor). Expert compute is a batched per-expert matmul
``einsum('ecd,edf->ecf')`` — MXU-shaped, and EP-shardable by putting the E
axis of the expert weights (and of the gathered token buffer) on the 'model'
mesh axis.

MoE routing is itself structured activation sparsity — the paper's C2 at
expert granularity; with ``relu_sparse`` the ReLU gather applies *inside*
the routed expert as well (composed byte savings, see DESIGN.md §5).

Serving takes ``moe_serve`` instead: drop-free, over the experts this chip
holds (``ModelConfig.held_experts``), with the router over all of them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import sparsity as sp
from repro.models import layers
from repro.models.ffn import init_ffn


def init_moe(key, cfg: ModelConfig, dtype):
    """Router over all ``n_experts``; weights of the held experts only."""
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (d, E), jnp.float32),
        "w_up": layers.dense_init(ks[1], (cfg.n_held, d, f), dtype),
        "w_down": layers.dense_init(ks[2], (cfg.n_held, f, d), dtype),
    }
    if cfg.glu:
        p["w_gate"] = layers.dense_init(ks[3], (cfg.n_held, d, f), dtype)
    if cfg.router_bias:
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = init_ffn(ks[4], cfg, dtype,
                               d_ff=cfg.d_ff * cfg.n_shared_experts)
    return p


def capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(tokens * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))
    return max(8, ((c + 7) // 8) * 8)  # pad to sublane multiple


def route(router_logits: jax.Array, cfg: ModelConfig, cap: int):
    """router_logits f32[T, E] -> dispatch tables.

    Returns:
      table:  i32[E, cap]   token id feeding each (expert, slot); T = dropped
      gates:  f32[E, cap]   combine weight per slot (0 for empty)
      aux:    load-balancing loss (Switch-style)
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)     # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    flat_e = expert_idx.reshape(-1)                             # [T*k]
    flat_g = gate_vals.reshape(-1)
    token_id = jnp.repeat(jnp.arange(T), cfg.top_k)

    # rank of each assignment within its expert via a stable sort
    # (O(n log n); the one-hot cumsum alternative costs O(n^2 * E) as a
    # reduce-window and dominates the MoE step's FLOPs at 1M tokens)
    order = jnp.argsort(flat_e, stable=True)                    # [T*k]
    sorted_e = jnp.take(flat_e, order)
    starts = jnp.searchsorted(sorted_e, jnp.arange(E),
                              side="left")                      # [E]
    rank_sorted = jnp.arange(flat_e.shape[0]) - jnp.take(starts, sorted_e)
    rank = jnp.zeros_like(flat_e).at[order].set(rank_sorted)

    keep = rank < cap
    slot = jnp.where(keep, rank, cap)                           # cap = spill
    e_safe = jnp.where(keep, flat_e, 0)

    table = jnp.full((E, cap + 1), T, jnp.int32)
    table = table.at[e_safe, slot].set(jnp.where(keep, token_id, T),
                                       mode="drop")
    gates = jnp.zeros((E, cap + 1), jnp.float32)
    gates = gates.at[e_safe, slot].set(jnp.where(keep, flat_g, 0.0),
                                       mode="drop")

    # aux loss: fraction of tokens per expert * mean router prob per expert
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32),
                  axis=0)
    aux = E * jnp.sum(me * ce)
    return table[:, :cap], gates[:, :cap], aux


def moe_forward(p, cfg: ModelConfig, x):
    """x: [B, S, d] -> ([B, S, d], aux_loss)."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    cap = capacity(T, cfg)
    table, gates, aux = route(xt @ p["router"], cfg, cap)

    from repro.dist.sharding import constrain_moe_dispatch as _ep

    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)
    table = _ep(table)
    xe = _ep(jnp.take(xpad, table, axis=0))                     # [E, cap, d]
    act = "relu" if cfg.relu_sparse else cfg.act
    up = _ep(jnp.einsum("ecd,edf->ecf", xe, p["w_up"]))
    if cfg.glu:
        g = sp.apply_act(
            _ep(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])), act)
        h = g * up
    else:
        h = sp.apply_act(up, act)
    ye = _ep(jnp.einsum("ecf,efd->ecd", h, p["w_down"]))        # [E, cap, d]
    ye = ye * gates[..., None].astype(ye.dtype)

    out = jnp.zeros((T + 1, d), ye.dtype)
    out = out.at[table.reshape(-1)].add(ye.reshape(-1, d), mode="drop")
    out = out[:T]

    if cfg.n_shared_experts:
        sh = p["shared"]
        out = out + sp.dense_ffn(xt, sh["w_up"], sh["w_down"], act=act,
                                 w_gate=sh.get("w_gate"))
    return out.reshape(B, S, d), aux


def moe_reference(p, cfg: ModelConfig, x):
    """Dense oracle: every expert computed for every token, combined by the
    full top-k gate. O(T*E*f) — tests only."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    probs = jax.nn.softmax((xt @ p["router"]).astype(jnp.float32), -1)
    vals, idx = jax.lax.top_k(probs, cfg.top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(xt.shape[0])[:, None], idx].set(vals)        # [T, E]
    act = "relu" if cfg.relu_sparse else cfg.act
    up = jnp.einsum("td,edf->tef", xt, p["w_up"])
    if cfg.glu:
        g = sp.apply_act(jnp.einsum("td,edf->tef", xt, p["w_gate"]), act)
        h = g * up
    else:
        h = sp.apply_act(up, act)
    ye = jnp.einsum("tef,efd->ted", h, p["w_down"])
    out = jnp.einsum("ted,te->td", ye, gates.astype(ye.dtype))
    if cfg.n_shared_experts:
        sh = p["shared"]
        out = out + sp.dense_ffn(xt, sh["w_up"], sh["w_down"], act=act,
                                 w_gate=sh.get("w_gate"))
    return out.reshape(B, S, d)


# ---------------------------------------------------------------------------
# Serving dispatch: drop-free over the held experts


def route_serve(p, cfg: ModelConfig, x):
    """The router in float32 (x [T, d] -> ids i32[T, k], weights f32[T, k]):
    scores over all ``n_experts`` (sigmoid or softmax); the top-k chosen
    by score plus the selection bias where the config has one (noaux_tc),
    weighted by the unbiased scores, renormalised over the top-k
    (``norm_topk_prob``) and scaled by ``routed_scaling_factor``."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits) if cfg.scoring_func == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    sel = s + p["router_bias"].astype(jnp.float32) if cfg.router_bias else s
    _, idx = jax.lax.top_k(sel, cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


def moe_serve(p, cfg: ModelConfig, x, valid):
    """The MoE layer of a serving step: x [..., d], valid bool[...] (False
    on padding positions). Each (valid token, held expert) assignment is
    computed exactly once — no capacity, no drops — so a token's output
    does not depend on the other tokens of its tick. Tokens routed to
    experts held on other chips get nothing from those here; the gate
    weights still normalise over all k choices. The held experts run as
    one dense product over every token, with the gate zero where a token
    did not choose the expert (at decode batch sizes the expert weights'
    stream, not these FLOPs, sets the time).

    Returns (y [..., d], stats i32[3]: held assignments, assignments to
    experts held elsewhere, the most tokens on one held expert)."""
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    valid = valid.reshape(-1)
    lo, hi = cfg.held_range
    with jax.named_scope("moe_router"):
        idx, w = route_serve(p, cfg, xt)
        mine = (idx[..., None] == jnp.arange(lo, hi)) \
            & valid[:, None, None]                          # [T, k, held]
        gates = jnp.sum(jnp.where(mine, w[..., None], 0.0), axis=1)
        load = jnp.sum(mine, axis=(0, 1), dtype=jnp.int32)
        held = jnp.sum(load)
        stats = jnp.stack([held, jnp.sum(valid, dtype=jnp.int32) * cfg.top_k
                           - held, jnp.max(load)])
    act = "relu" if cfg.relu_sparse else cfg.act
    with jax.named_scope("moe_experts"):
        up = jnp.einsum("td,edf->etf", xt, p["w_up"])
        if cfg.glu:
            h = sp.apply_act(jnp.einsum("td,edf->etf", xt, p["w_gate"]),
                             act) * up
        else:
            h = sp.apply_act(up, act)
        ye = jnp.einsum("etf,efd->etd", h, p["w_down"])
        y = jnp.einsum("etd,te->td", ye.astype(jnp.float32), gates)
    if cfg.n_shared_experts:
        with jax.named_scope("ffn_dense"):
            sh = p["shared"]
            y = y + sp.dense_ffn(xt, sh["w_up"], sh["w_down"], act=act,
                                 w_gate=sh.get("w_gate"))
    return y.astype(x.dtype).reshape(shape), stats
