"""Plain float32 reference of the latent-attention MoE decoder (DeepSeek-V3
family: Moonlight-16B-A3B).

Straight ``jax.numpy`` at ``highest`` matmul precision, with no cache, no
paging and no batching: one sequence at a time, one layer at a time, with
attention computed over blocks of queries so that a long sequence fits
beside the served model's weights. It imports nothing of the program; it
reads the weights the benchmark made (``bench/families/mla_moe.py``),
upcasting one layer's leaves at a time.

Per layer, with x the RMS-normed input:

* attention, expanded (the program attends in latent space instead):
  q = x Wq split per head into q_nope and q_pe; [c, k_pe] = x Wkv_a, c
  RMS-normed, k_pe roped once and shared by the heads; [k_nope, v] =
  c Wkv_b per head; softmax((q_nope.k_nope + rope(q_pe).k_pe) /
  sqrt(qk_nope + qk_rope)) over v, then Wo;
* the first ``first_k_dense_replace`` layers: a dense SwiGLU;
* the others: s = sigmoid(x Wr) over all ``published.n_routed_experts``
  experts in float32; the top-k of s + bias selected; w_i = scale * s_i /
  sum of the top-k s; the output is sum of w_i SwiGLU_i(x) over the
  selected experts this chip holds (the configuration's held range, as in
  the program: experts held on other chips add nothing here), plus the
  shared SwiGLU.

``quant=True`` is the control: the same computation held in float8
(e4m3) where the bf16 program holds bf16, as in ``dense_gqa.py``: every
projection (the router and each expert included) and the logits from
float8 operands, and the residual stream rounded to float8 after the
embedding and after every layer's two additions.

Departures from the published model: random weights; rope pairs halves
(x[:h], x[h:]), where the checkpoint interleaves its rope columns — the
same map under a fixed permutation of Wq's and Wkv_a's rope columns;
``e_score_correction_bias`` drawn from the seed (it is learned).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_gqa import (_bucket, _fp8, _held, _mm, _rms,
                                       _rope, _scores)

Q_BLOCK = 512


def held_range(conf: dict):
    """Experts [lo, hi) of the published count whose weights are here."""
    lo, hi = conf["held_experts"]
    return int(lo), int(hi)


def _attend(q, k, v, block: int = Q_BLOCK):
    """Causal attention of q/k [L, H, dqk] and v [L, H, dv] -> [L, H, dv],
    scale 1/sqrt(dqk), over blocks of ``block`` queries (L a multiple)."""
    L, H, dqk = q.shape
    keys = jnp.arange(L)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * dqk ** -0.5
        pos = start + jnp.arange(block)
        s = jnp.where(keys[None, None, :] <= pos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    return jax.lax.map(one, jnp.arange(0, L, block)).reshape(L, H, -1)


def _swiglu(h, f, quant):
    y = jax.nn.silu(_mm(h, f["w_gate"], quant)) * _mm(h, f["w_up"], quant)
    return _mm(y, f["w_down"], quant)


def _emm(x, w, quant):
    """x [L, k] through each expert's w [E, k, n] -> [E, L, n]."""
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 1)
    return jnp.einsum("lk,ekn->eln", x, w)


def _moe(h, m, conf, quant):
    lo, hi = held_range(conf)
    s = jax.nn.sigmoid(_mm(h, m["router"], quant))        # [L, E] f32
    _, idx = jax.lax.top_k(s + m["router_bias"], conf["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if conf["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * conf["routed_scaling_factor"]
    gate = jnp.sum(jnp.where(idx[..., None] == jnp.arange(lo, hi), w[..., None],
                             0.0), axis=1)                  # [L, held]
    y = jax.nn.silu(_emm(h, m["w_gate"], quant)) * _emm(h, m["w_up"], quant)
    if quant:
        y = _fp8(y, -1)
        down = jnp.einsum("elf,efd->eld", y, _fp8(m["w_down"], 1))
    else:
        down = jnp.einsum("elf,efd->eld", y, m["w_down"])
    return jnp.einsum("eld,le->ld", down, gate) + _swiglu(h, m["shared"],
                                                          quant)


@functools.partial(jax.jit,
                   static_argnames=("conf_items", "quant", "dense", "block"))
def _layer(x, stack, i, conf_items, quant, dense, block=Q_BLOCK):
    conf = dict(conf_items)
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack["b0"])
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    H, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    nope, dv = conf["qk_nope_head_dim"], conf["v_head_dim"]
    L = x.shape[0]
    a = p["attn"]
    h = _rms(x, p["norm1"], eps)
    q = _mm(h, a["wq"], quant).reshape(L, H, -1)
    ckv = _mm(h, a["wkv_a"], quant)
    c = _rms(ckv[:, :r], a["kv_norm"], eps)
    k_pe = _rope(ckv[:, None, r:], theta)                  # [L, 1, rope]
    kv = _mm(c, a["wkv_b"], quant).reshape(L, H, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (L, H, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    o = _attend(q, k, kv[..., nope:], block)
    x = _held(x + _mm(o.reshape(L, -1), a["wo"], quant), quant)
    h = _rms(x, p["norm2"], eps)
    y = _swiglu(h, p["ffn"], quant) if dense else _moe(h, p["moe"], conf,
                                                       quant)
    return _held(x + y, quant)


def _conf_items(conf: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool, list))))


def forward_hidden(conf: dict, params, tokens: np.ndarray, quant: bool,
                   block: int = Q_BLOCK):
    """Final-layer hidden states [Lp, d] (f32) of one sequence, padded at
    the end to a power-of-two bucket (padding cannot reach earlier
    positions through the causal mask)."""
    items = _conf_items(conf)
    Lp = _bucket(len(tokens), block)
    toks = np.zeros((Lp,), np.int32)
    toks[:len(tokens)] = tokens
    x = _held(params["embed"][jnp.asarray(toks)].astype(jnp.float32), quant)
    with jax.default_matmul_precision("highest"):
        for i in range(conf["first_k_dense_replace"]):
            x = _layer(x, params["lead"], i, items, quant, True, block)
        for i in range(conf["num_hidden_layers"]
                       - conf["first_k_dense_replace"]):
            x = _layer(x, params["units"], i, items, quant, False, block)
    return x


def served_scores(conf: dict, params, prompt: np.ndarray,
                  served: np.ndarray, control: bool = False):
    """For one request, at each served position: the gap between the
    reference's best logit and its logit of the served token (0 where
    they agree), and the reference's log-probability of the served token.
    With ``control``: the gap of the token the float8 control puts first,
    and the control's log-probability of the served token."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    P, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]])
    npos = _bucket(n, 64)
    pos = np.full((npos,), P - 1 + n - 1, np.int32)
    pos[:n] = np.arange(P - 1, P - 1 + n)
    chosen = np.zeros((npos,), np.int32)
    chosen[:n] = served
    with jax.default_matmul_precision("highest"):
        x = forward_hidden(conf, params, seq, quant=False)
        x_ctl = forward_hidden(conf, params, seq, quant=True) \
            if control else None
        gap, lp = _scores(x, x_ctl, jnp.asarray(pos), params["final_norm"],
                          params["head"], jnp.asarray(chosen),
                          float(conf["rms_norm_eps"]))
    return np.asarray(gap)[:n], np.asarray(lp)[:n]
