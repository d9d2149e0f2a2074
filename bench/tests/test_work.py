"""The needed-work counts, against a count made by hand at smoke size
(the widths of the ``qwen3-4b-smoke`` registry entry)."""

import pytest

from bench import work
from bench.tests import smoke

# d 64, d_ff 128, 4 heads of 16 (q 64 wide), 2 KV heads (k, v 32 wide),
# 2 layers, vocab 256, tied embeddings, qk-norm, float32 (4 bytes).
MATMUL = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128   # 36864
LAYER = MATMUL + 2 * 64 + 2 * 16                          # 37024
KV_TOKEN = 2 * 2 * 2 * 16 * 4                             # 512 bytes


def test_layer_counts():
    assert work.layer_matmul_params(smoke.CONF) == MATMUL
    assert work.layer_params(smoke.CONF) == LAYER
    assert work.kv_bytes_per_token(smoke.CONF) == KV_TOKEN


def test_one_decode_row():
    # one token at position 10: 2 x matmul params x 2 layers, attention
    # over 11 keys (4 x 2 layers x 4 heads x 16 x 11), logits 2 x 64 x 256
    w = work.tick_work(smoke.CONF, [(1, 10, True)])
    assert w.flops == 2 * 2 * MATMUL + 4 * 2 * 4 * 16 * 11 + 2 * 64 * 256
    weights = (2 * LAYER + 64) * 4 + 256 * 64 * 4
    assert w.bytes == weights + 11 * KV_TOKEN


def test_prefill_chunk_unsampled():
    # 3 tokens at positions 5, 6, 7: they see 6 + 7 + 8 = 21 keys; no
    # logits; the embedding rows of the 3 tokens only
    w = work.tick_work(smoke.CONF, [(3, 5, False)])
    assert w.flops == 3 * 2 * 2 * MATMUL + 4 * 2 * 4 * 16 * 21
    assert w.bytes == (2 * LAYER + 64) * 4 + 3 * 64 * 4 + 8 * KV_TOKEN


def test_rows_add():
    a = work.tick_work(smoke.CONF, [(1, 10, True)])
    b = work.tick_work(smoke.CONF, [(1, 10, True), (3, 5, False)])
    extra = 3 * 2 * 2 * MATMUL + 4 * 2 * 4 * 16 * 21
    assert b.flops == a.flops + extra
    assert b.bytes == a.bytes + 8 * KV_TOKEN


def test_bias_and_untied():
    conf = dict(smoke.CONF, qk_norm=False, qkv_bias=True,
                tie_word_embeddings=False)
    assert work.layer_params(conf) == MATMUL + 2 * 64 + 64 + 2 * 32
    w = work.tick_work(conf, [(2, 0, True)])
    weights = (2 * work.layer_params(conf) + 64) * 4 + 256 * 64 * 4 \
        + 2 * 64 * 4
    assert w.bytes == weights + 2 * KV_TOKEN


def test_least_seconds_binding_term():
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    c, m = work.least_seconds(work.Work(2e12, 5e8), peak)
    assert (c, m) == pytest.approx((2.0, 0.5))
