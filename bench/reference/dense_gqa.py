"""Plain float32 reference of the dense GQA decoder (Qwen2/Qwen3 family).

Straight ``jax.numpy`` at ``highest`` matmul precision, with no cache, no
paging and no batching: one sequence at a time, one layer at a time, with
attention computed over blocks of queries so that a long sequence fits
beside the served model's weights. It imports nothing of the program; it
reads the weights the benchmark made (``bench/families/dense_gqa.py``),
upcasting one layer's leaves at a time.

``quant=True`` is the control: the same computation held in float8
(e4m3) where the bf16 program holds bf16: every projection and the
logits from float8 operands (activations scaled per token, weights per
output column), and the residual stream rounded to float8 after the
embedding and after every layer's two additions. It is the step below
the bf16 the configurations state.

What the benchmark compares, for each served token: the program's
log-probability (``SamplingParams(logprobs=True)``, from the logits it
sampled from) against this reference's at the same position, and the
gap by which the served token's logit lies below the reference's best.

Departures from the published models: none in the mathematics; weights
are random.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _head_dim(conf):
    return int(conf.get("head_dim")
               or conf["hidden_size"] // conf["num_attention_heads"])


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _fp8(x, axis):
    """Round to float8 e4m3, scaled so that the largest magnitude along
    ``axis`` maps to e4m3's largest (448)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _held(x, quant):
    """The residual stream as the layer hands it on: float8 under
    ``quant``, as the bf16 program holds it in bf16."""
    return _fp8(x, -1) if quant else x


def _mm(x, w, quant):
    """x [..., k] @ w [k, n]; float8 operands under ``quant``."""
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rope(x, theta):
    """x [L, H, dh]; positions 0..L-1; halves paired (x[:h], x[h:])."""
    L, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attend(q, k, v):
    """Causal GQA attention. q [L, nq, dh], k/v [L, nkv, dh] -> [L, nq, dh],
    over blocks of Q_BLOCK queries."""
    L, nq, dh = q.shape
    g = nq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    keys = jnp.arange(L)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * dh ** -0.5
        pos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(keys[None, None, :] <= pos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, jnp.arange(0, L, Q_BLOCK))
    return out.reshape(L, nq, dh)


@functools.partial(jax.jit, static_argnames=("conf_items", "quant"))
def _layer(x, units, i, conf_items, quant):
    conf = dict(conf_items)
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), units["b0"])
    eps, dh = conf["rms_norm_eps"], _head_dim(conf)
    L = x.shape[0]
    a = p["attn"]
    h = _rms(x, p["norm1"], eps)
    q, k, v = _mm(h, a["wq"], quant), _mm(h, a["wk"], quant), \
        _mm(h, a["wv"], quant)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = q.reshape(L, -1, dh)
    k = k.reshape(L, -1, dh)
    v = v.reshape(L, -1, dh)
    if "q_norm" in a:
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
    x = _held(x + _mm(_attend(q, k, v).reshape(L, -1), a["wo"], quant),
              quant)
    f = p["ffn"]
    h = _rms(x, p["norm2"], eps)
    y = jax.nn.silu(_mm(h, f["w_gate"], quant)) * _mm(h, f["w_up"], quant)
    return _held(x + _mm(y, f["w_down"], quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(x, pos, final_norm, out_w, eps, quant):
    h = _rms(x[pos], final_norm.astype(jnp.float32), eps)
    return _mm(h, out_w.astype(jnp.float32), quant)


def _take(z, tok):
    return jnp.take_along_axis(z, tok[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("eps",))
def _scores(x, x_ctl, pos, final_norm, out_w, served, eps):
    """At each position: the reference's best logit minus its logit of
    the token picked, and the served token's log-probability. Without a
    control (``x_ctl`` None) the token picked is the served one and the
    log-probability the reference's; with it, both are the control's (the
    token picked is the one the control puts first)."""
    z = _logits(x, pos, final_norm, out_w, eps, False)
    if x_ctl is None:
        zp, pick = z, served
    else:
        zp = _logits(x_ctl, pos, final_norm, out_w, eps, True)
        pick = jnp.argmax(zp, -1).astype(served.dtype)
    lp = _take(zp, served) - jax.nn.logsumexp(zp, -1)
    return z.max(-1) - _take(z, pick), lp


def _bucket(n: int, least: int = Q_BLOCK) -> int:
    b = least
    while b < n:
        b *= 2
    return b


def forward_hidden(conf: dict, params, tokens: np.ndarray, quant: bool):
    """Final-layer hidden states [Lp, d] (f32) of one sequence, padded at
    the end to a power-of-two bucket (padding cannot reach earlier
    positions through the causal mask)."""
    conf_items = tuple(sorted((k, v) for k, v in conf.items()
                              if isinstance(v, (int, float, str, bool))))
    Lp = _bucket(len(tokens))
    toks = np.zeros((Lp,), np.int32)
    toks[:len(tokens)] = tokens
    x = _held(params["embed"][jnp.asarray(toks)].astype(jnp.float32), quant)
    with jax.default_matmul_precision("highest"):
        for i in range(conf["num_hidden_layers"]):
            x = _layer(x, params["units"], i, conf_items, quant)
    return x


def _out_weight(conf, params):
    return params["embed"].T if conf["tie_word_embeddings"] \
        else params["head"]


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
                          _out_weight(conf, params), jnp.asarray(chosen),
                          float(conf["rms_norm_eps"]))
    return np.asarray(gap)[:n], np.asarray(lp)[:n]
