"""Dense decoder with grouped-query attention, RoPE, RMSNorm and a SiLU GLU
FFN (Qwen2/Qwen3, Llama-like): how a configuration file of this family
becomes the program's ``ModelConfig``, and its random weights.

The weights are made here, from the seed, in the program's parameter
layout and in the type they are served in, by one jitted call on the
device. The plain reference (``bench/reference/dense_gqa.py``) reads the
same arrays; neither side takes weights the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def head_dim(conf: dict) -> int:
    return int(conf.get("head_dim")
               or conf["hidden_size"] // conf["num_attention_heads"])


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        d_head=head_dim(conf), act=conf["hidden_act"], glu=True,
        qk_norm=bool(conf.get("qk_norm", False)),
        qkv_bias=bool(conf.get("qkv_bias", False)),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        norm_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
        dtype=conf["torch_dtype"])


def _normal(key, shape, std, dtype):
    """Truncated normal drawn in the served type itself, so that no
    float32 copy of a stacked leaf is ever made on the device."""
    return jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype) \
        * jnp.asarray(std, dtype)


def _scale(key, shape, dtype):
    return 1.0 + jax.random.uniform(key, shape, dtype, -0.1, 0.1)


def init_weights(conf: dict, key):
    """Parameter tree in the program's layout (layers stacked on a leading
    axis under ``units/b0``). Call under ``jax.jit`` with ``conf`` closed
    over, so that every leaf is made on the device in its served type."""
    dtype = jnp.dtype(conf["torch_dtype"])
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    f, V = conf["intermediate_size"], conf["vocab_size"]
    nq, nkv, dh = (conf["num_attention_heads"],
                   conf["num_key_value_heads"], head_dim(conf))
    ks = iter(jax.random.split(key, 20))
    attn = {"wq": _normal(next(ks), (L, d, nq * dh), d ** -0.5, dtype),
            "wk": _normal(next(ks), (L, d, nkv * dh), d ** -0.5, dtype),
            "wv": _normal(next(ks), (L, d, nkv * dh), d ** -0.5, dtype),
            "wo": _normal(next(ks), (L, nq * dh, d), (nq * dh) ** -0.5,
                          dtype)}
    if conf.get("qkv_bias"):
        attn["bq"] = _normal(next(ks), (L, nq * dh), 0.5, dtype)
        attn["bk"] = _normal(next(ks), (L, nkv * dh), 0.5, dtype)
        attn["bv"] = _normal(next(ks), (L, nkv * dh), 0.5, dtype)
    if conf.get("qk_norm"):
        attn["q_norm"] = _scale(next(ks), (L, dh), dtype)
        attn["k_norm"] = _scale(next(ks), (L, dh), dtype)
    block = {"norm1": _scale(next(ks), (L, d), dtype), "attn": attn,
             "norm2": _scale(next(ks), (L, d), dtype),
             "ffn": {"w_up": _normal(next(ks), (L, d, f), d ** -0.5, dtype),
                     "w_gate": _normal(next(ks), (L, d, f), d ** -0.5,
                                       dtype),
                     "w_down": _normal(next(ks), (L, f, d), f ** -0.5,
                                       dtype)}}
    params = {"embed": _normal(next(ks), (V, d), d ** -0.5, dtype),
              "final_norm": _scale(next(ks), (d,), dtype),
              "units": {"b0": block}}
    if not conf["tie_word_embeddings"]:
        params["head"] = _normal(next(ks), (d, V), d ** -0.5, dtype)
    return params
