"""The work a serving tick needs, whatever implements it: FLOPs and HBM
bytes of a dense GQA decoder at the model's served type, from its shapes.

A tick is a list of rows ``(n_tokens, ctx_before, sampled)``: a row feeds
``n_tokens`` new tokens at positions ``ctx_before ..`` and samples one
token from its last position when ``sampled``. What counts:

* FLOPs: 2 x the non-embedding matmul parameters per token processed;
  attention over the real context (QK^T and PV, 4 x heads x head_dim per
  key visible to each query); the vocabulary projection for sampled
  positions only.
* bytes: every non-embedding weight once per tick; the output projection
  (the embedding, when tied) once if any position is sampled; the KV of
  each row's real context read once and its new tokens' KV written once.

So a program that gathers padding, projects every position or copies its
cache does more than this, and its roofline share shows it.
"""

from __future__ import annotations

from typing import Iterable, NamedTuple, Tuple


class Work(NamedTuple):
    flops: float
    bytes: float


def _dims(conf: dict):
    d, f = conf["hidden_size"], conf["intermediate_size"]
    nq, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = int(conf.get("head_dim") or d // nq)
    return d, f, nq, nkv, dh, conf["num_hidden_layers"], conf["vocab_size"]


def dtype_bytes(conf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["torch_dtype"]]


def layer_matmul_params(conf: dict) -> int:
    d, f, nq, nkv, dh, _, _ = _dims(conf)
    return d * nq * dh + 2 * d * nkv * dh + nq * dh * d + 3 * d * f


def layer_params(conf: dict) -> int:
    """Matmul weights plus biases and norm scales of one layer."""
    d, _, nq, nkv, dh, _, _ = _dims(conf)
    extra = 2 * d
    if conf.get("qkv_bias"):
        extra += (nq + 2 * nkv) * dh
    if conf.get("qk_norm"):
        extra += 2 * dh
    return layer_matmul_params(conf) + extra


def kv_bytes_per_token(conf: dict) -> int:
    _, _, _, nkv, dh, L, _ = _dims(conf)
    return 2 * L * nkv * dh * dtype_bytes(conf)


def tick_work(conf: dict, rows: Iterable[Tuple[int, int, bool]]) -> Work:
    d, _, nq, _, dh, L, V = _dims(conf)
    eb = dtype_bytes(conf)
    per_tok = 2 * L * layer_matmul_params(conf)
    flops = 0.0
    kv_tokens = 0
    n_tok = 0
    sampled = 0
    for n, ctx, smp in rows:
        n_tok += n
        # query at absolute position p sees p + 1 keys
        keys = n * ctx + n * (n + 1) // 2
        flops += per_tok * n + 4 * L * nq * dh * keys
        kv_tokens += ctx + n
        sampled += int(bool(smp))
    flops += 2 * d * V * sampled
    weights = (L * layer_params(conf) + d) * eb
    weights += V * d * eb if sampled else n_tok * d * eb
    if not conf["tie_word_embeddings"]:
        weights += n_tok * d * eb          # the input embedding rows
    return Work(float(flops),
                float(weights + kv_tokens * kv_bytes_per_token(conf)))


def least_seconds(work: Work, peak: dict) -> Tuple[float, float]:
    """(compute-bound seconds, memory-bound seconds) at the chip's peaks."""
    return (work.flops / peak["bf16_flops_per_s"],
            work.bytes / peak["hbm_bytes_per_s"])
