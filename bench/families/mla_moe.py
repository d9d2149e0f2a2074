"""Decoder with latent attention (MLA) and fine-grained routed experts
(DeepSeek-V3 family: Moonlight-16B-A3B): how a configuration file of this
family becomes the program's ``ModelConfig``, and its random weights.

A file of this family states the experts held on this chip: the router
spans ``published.n_routed_experts``, the weights are those of the
``n_routed_experts`` experts ``held_experts`` = [lo, hi) of each MoE
layer, as one chip of an expert-parallel deployment holds them.

The weights are made here, from the seed, in the program's parameter
layout and in the type they are served in (the selection bias in float32,
as published), by one jitted call on the device. The plain reference
(``bench/reference/mla_moe.py``) reads the same arrays; neither side takes
weights the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.families.dense_gqa import _normal, _scale


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["moe_intermediate_size"], vocab=conf["vocab_size"],
        act=conf["hidden_act"], glu=True,
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        norm_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        n_experts=conf["published"]["n_routed_experts"],
        top_k=conf["num_experts_per_tok"],
        n_shared_experts=conf["n_shared_experts"],
        first_k_dense_replace=conf["first_k_dense_replace"],
        dense_d_ff=conf["intermediate_size"],
        scoring_func=conf["scoring_func"],
        router_bias=conf["topk_method"] == "noaux_tc",
        norm_topk_prob=bool(conf["norm_topk_prob"]),
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        held_experts=tuple(conf["held_experts"]),
        dtype=conf["torch_dtype"])


def _mla(ks, conf, n, dtype):
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    r, rope = conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    nope, dv = conf["qk_nope_head_dim"], conf["v_head_dim"]
    return {"wq": _normal(next(ks), (n, d, H * (nope + rope)), d ** -0.5,
                          dtype),
            "wkv_a": _normal(next(ks), (n, d, r + rope), d ** -0.5, dtype),
            "kv_norm": _scale(next(ks), (n, r), dtype),
            "wkv_b": _normal(next(ks), (n, r, H * (nope + dv)), r ** -0.5,
                             dtype),
            "wo": _normal(next(ks), (n, H * dv, d), (H * dv) ** -0.5, dtype)}


def _swiglu(ks, lead, d, f, dtype):
    return {"w_up": _normal(next(ks), lead + (d, f), d ** -0.5, dtype),
            "w_gate": _normal(next(ks), lead + (d, f), d ** -0.5, dtype),
            "w_down": _normal(next(ks), lead + (f, d), f ** -0.5, dtype)}


def init_weights(conf: dict, key):
    """Parameter tree in the program's layout: the leading dense layers
    stacked under ``lead/b0``, the MoE layers under ``units/b0``. Call
    under ``jax.jit`` with ``conf`` closed over, so that every leaf is
    made on the device in its served type. Refuses a program that cannot
    serve this family (its ``ModelConfig`` lacks the fields) before any
    weight is made."""
    model_config(conf)
    dtype = jnp.dtype(conf["torch_dtype"])
    d, V = conf["hidden_size"], conf["vocab_size"]
    n_lead = conf["first_k_dense_replace"]
    n_moe = conf["num_hidden_layers"] - n_lead
    E, held = conf["published"]["n_routed_experts"], conf["n_routed_experts"]
    f = conf["moe_intermediate_size"]
    ks = iter(jax.random.split(key, 32))

    def block(n):
        return {"norm1": _scale(next(ks), (n, d), dtype),
                "attn": _mla(ks, conf, n, dtype),
                "norm2": _scale(next(ks), (n, d), dtype)}

    lead = dict(block(n_lead), ffn=_swiglu(ks, (n_lead,), d,
                                           conf["intermediate_size"], dtype))
    moe = {"router": _normal(next(ks), (n_moe, d, E), d ** -0.5, dtype),
           "router_bias": jax.random.uniform(next(ks), (n_moe, E),
                                             jnp.float32, -0.05, 0.05),
           **_swiglu(ks, (n_moe, held), d, f, dtype),
           "shared": _swiglu(ks, (n_moe,), d, f * conf["n_shared_experts"],
                             dtype)}
    return {"embed": _normal(next(ks), (V, d), d ** -0.5, dtype),
            "head": _normal(next(ks), (d, V), d ** -0.5, dtype),
            "final_norm": _scale(next(ks), (d,), dtype),
            "lead": {"b0": lead},
            "units": {"b0": dict(block(n_moe), moe=moe)}}
