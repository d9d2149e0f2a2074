"""A cell at smoke size for the CPU tests: the widths of the
``qwen3-4b-smoke`` and ``qwen2.5-3b-smoke`` registry entries, float32."""

from __future__ import annotations

from bench import harness

CONF = {
    "name": "qwen3-smoke", "family": "dense_gqa", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
    "tie_word_embeddings": True, "torch_dtype": "float32", "qk_norm": True,
}

MIX = {
    "arrival": {"process": "poisson"},
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                   "min": 4, "max": 96},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                   "min": 2, "max": 24},
}

SETTINGS = {
    "rate_per_s": 8.0,
    "serve": {"max_batch": 4, "max_seq": 128, "block_size": 16,
              "prefill_chunk": 16, "n_kv_blocks": 32},
    "check": {"max_logprob_err": 1e-3, "max_logit_gap": 1e-3},
}


def cell(conf=None, mix=None, settings=None, spec=None) -> harness.Cell:
    spec = spec or {"end_to_end": [], "per_layer": []}
    return harness.Cell(name="smoke.chat", chips=1, conf=conf or CONF,
                        mix=mix or MIX, settings=settings or SETTINGS,
                        spec=spec)
