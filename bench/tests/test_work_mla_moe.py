"""The latent-attention MoE work count against a count made by hand at
smoke size (the shapes of bench/tests/test_mla_moe.py's CONF)."""

import pytest

from bench import work_mla_moe
from bench.tests.test_mla_moe import CONF

# d 64, 4 heads, latent 32 + rope 8, q/k nope 16, v 16; 3 layers (1 dense
# of width 96, 2 MoE); router over 8 experts, 4 held, top-2, experts of
# width 32, 2 shared; vocab 256, untied; float32 (4 bytes).
ATTN = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64    # 16896
DENSE = 3 * (ATTN + 32 + 2 * 64) + 3 * 64 * 96 \
    + 2 * (3 * 64 * 64 + 64 * 8) + 64                      # 95264
EXPERT = 3 * 64 * 32                                         # 6144
LATENT_TOKEN = 3 * 4 * 40                                    # 480 bytes


def test_layer_counts():
    assert DENSE == 95264
    assert work_mla_moe.dense_params(CONF) == DENSE
    assert work_mla_moe.expert_params(CONF) == EXPERT
    assert work_mla_moe.latent_bytes_per_token(CONF) == LATENT_TOKEN
    assert work_mla_moe.experts_reached(CONF, 1) == pytest.approx(1.0)
    assert work_mla_moe.experts_reached(CONF, 4) == pytest.approx(
        4 * (1 - 0.75 ** 4))


def test_two_row_tick():
    # a decode row at position 10 (sampled) and a 3-token prefill chunk
    # at positions 5-7: 4 tokens, 11 + (6 + 7 + 8) = 32 visible keys
    w = work_mla_moe.tick_work(CONF, [(1, 10, True), (3, 5, False)])
    per_tok = 2 * (3 * ATTN + 3 * 64 * 96 + 2 * (3 * 64 * 64 + 64 * 8)
                   + 2 * 2 * 4 / 8 * EXPERT)                 # 214016
    per_key = 2 * 4 * (2 * 32 + 8) * 3                       # 1728
    assert w.flops == 4 * per_tok + 32 * per_key + 2 * 64 * 256
    assert w.flops == 944128
    # every non-expert weight and the router's f32 bias once; the held
    # experts 4 tokens reach in each MoE layer; the head; 4 embedding
    # rows; the latent of 11 + 8 context-and-new tokens
    reached = 4 * (1 - 0.75 ** 4)
    assert w.bytes == pytest.approx(
        DENSE * 4 + 2 * 8 * 4 + 2 * reached * EXPERT * 4 + 256 * 64 * 4
        + 4 * 64 * 4 + 19 * LATENT_TOKEN)
    assert w.bytes == pytest.approx(591200)


def test_unsampled_tick_reads_no_head():
    a = work_mla_moe.tick_work(CONF, [(3, 5, False)])
    b = work_mla_moe.tick_work(CONF, [(3, 5, True)])
    assert b.bytes - a.bytes == 256 * 64 * 4
    assert b.flops - a.flops == 2 * 64 * 256
