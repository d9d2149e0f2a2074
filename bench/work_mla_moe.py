"""The work a serving tick needs, whatever implements it: FLOPs and HBM
bytes of a latent-attention MoE decoder (DeepSeek-V3 family) at the
model's served type, from its shapes, for the experts this chip holds.

A tick is a list of rows ``(n_tokens, ctx_before, sampled)`` as in
``bench/work.py``. What counts:

* bytes: every weight outside the routed experts once per tick (attention
  of every layer, the leading dense FFN, each MoE layer's router, bias
  and shared experts, the final norm); of each MoE layer's held experts,
  those the tick's T tokens reach, taken as the expectation under a
  router that spreads its k choices evenly over the E experts,
  ``held x (1 - (1 - k/E)^T)`` experts (the seeded router's expectation);
  the head once if any position is sampled, and the embedding rows of the
  tick's tokens; the latent cache, ``kv_lora_rank + qk_rope_head_dim``
  float32 values a layer, of each row's real context read once and of its
  new tokens written once.
* FLOPs: 2 x the matmul parameters each token meets (attention
  projections, the leading dense FFN or each MoE layer's shared experts
  and router), 2 x one expert's parameters for each held assignment, T x
  k x held/E a layer; latent attention, 2 x heads x (2 kv_lora_rank +
  qk_rope_head_dim) per key visible to each query and layer; the
  vocabulary projection for sampled positions only.

So a program that gathers padding, runs experts no token chose, or
projects every position does more than this, and its roofline share
shows it.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from bench.work import Work, dtype_bytes

LATENT_BYTES = 4          # the engine's float32 latent pool


def _attn_params(conf: dict) -> int:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    r, rope = conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    nope, dv = conf["qk_nope_head_dim"], conf["v_head_dim"]
    return d * H * (nope + rope) + d * (r + rope) + r * H * (nope + dv) \
        + H * dv * d


def _swiglu_params(conf: dict, f: int) -> int:
    return 3 * conf["hidden_size"] * f


def _layers(conf: dict) -> Tuple[int, int]:
    lead = conf["first_k_dense_replace"]
    return lead, conf["num_hidden_layers"] - lead


def expert_params(conf: dict) -> int:
    return _swiglu_params(conf, conf["moe_intermediate_size"])


def dense_params(conf: dict) -> int:
    """Weights outside the routed experts, embedding and head excluded,
    in elements (norm scales included; the router's bias is counted in
    ``tick_work`` at its float32 width)."""
    d = conf["hidden_size"]
    lead, moe = _layers(conf)
    E = conf["published"]["n_routed_experts"]
    attn = _attn_params(conf) + conf["kv_lora_rank"] + 2 * d
    shared = _swiglu_params(conf, conf["moe_intermediate_size"]
                            * conf["n_shared_experts"])
    return (lead + moe) * attn \
        + lead * _swiglu_params(conf, conf["intermediate_size"]) \
        + moe * (shared + d * E) + d


def experts_reached(conf: dict, n_tokens: int) -> float:
    """Held experts of one layer that n_tokens reach, in expectation."""
    E = conf["published"]["n_routed_experts"]
    k = conf["num_experts_per_tok"]
    return conf["n_routed_experts"] * (1.0 - (1.0 - k / E) ** n_tokens)


def latent_bytes_per_token(conf: dict) -> int:
    return conf["num_hidden_layers"] * LATENT_BYTES \
        * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"])


def tick_work(conf: dict, rows: Iterable[Tuple[int, int, bool]]) -> Work:
    d, V = conf["hidden_size"], conf["vocab_size"]
    H, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    rope, k = conf["qk_rope_head_dim"], conf["num_experts_per_tok"]
    E, held = conf["published"]["n_routed_experts"], \
        conf["n_routed_experts"]
    lead, moe = _layers(conf)
    eb = dtype_bytes(conf)
    shared = _swiglu_params(conf, conf["moe_intermediate_size"]
                            * conf["n_shared_experts"])
    per_tok = 2 * ((lead + moe) * _attn_params(conf)
                   + lead * _swiglu_params(conf, conf["intermediate_size"])
                   + moe * (shared + d * E)
                   + moe * k * held / E * expert_params(conf))
    per_key = 2 * H * (2 * r + rope) * (lead + moe)
    flops = 0.0
    n_tok = ctx_tokens = sampled = 0
    for n, ctx, smp in rows:
        n_tok += n
        flops += per_tok * n + per_key * (n * ctx + n * (n + 1) // 2)
        ctx_tokens += ctx + n
        sampled += int(bool(smp))
    flops += 2 * d * V * sampled
    weights = dense_params(conf) * eb + moe * E * 4 \
        + moe * experts_reached(conf, n_tok) * expert_params(conf) * eb \
        + (V * d * eb if sampled else 0) + n_tok * d * eb
    return Work(float(flops),
                float(weights + ctx_tokens * latent_bytes_per_token(conf)))
