"""Public model API: init / forward / loss / prefill / decode."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer


class Model:
    """Functional facade over the decoder stack for one config."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # --- params ---
    def init(self, key) -> Dict[str, Any]:
        return transformer.init_params(key, self.cfg)

    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    # --- training ---
    def loss(self, params, batch: dict):
        return transformer.loss_fn(params, self.cfg, batch)

    def forward(self, params, batch: dict):
        """Hidden states + logits (small-scale/eval use)."""
        hidden, aux, _ = transformer.forward(params, self.cfg, batch)
        return transformer.project_logits(params, self.cfg, hidden), aux

    # --- serving ---
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return transformer.init_cache(self.cfg, batch, max_len, dtype)

    def prefill(self, params, batch: dict, cache):
        """Process a prompt of S tokens, fill the cache, return logits of
        the last position and the updated cache."""
        hidden, _, new_cache = transformer.forward(
            params, self.cfg, batch, cache=cache)
        last = hidden[:, -1:]
        logits = transformer.project_logits(params, self.cfg, last)
        return logits, new_cache

    def decode_step(self, params, tokens, cache,
                    batch_extra: Optional[dict] = None):
        return transformer.decode_step(params, self.cfg, tokens, cache,
                                       batch_extra=batch_extra)

    # --- paged serving (block-table KV; see repro.serve.paged_kv) ---
    @property
    def _serving_precision(self) -> str:
        """A float32 config serves in float32. JAX's default precision
        would give its matmuls a single bf16 pass on a TPU, and the
        ReLU-sparse decode FFN's top-k then picks a different active set
        than float32 math would (a reordering at the cutoff swaps whole
        W_down rows)."""
        return "highest" if self.cfg.dtype == "float32" else "default"

    def init_paged_cache(self, batch: int, n_blocks: int, block_size: int,
                         max_blocks_per_seq: int, dtype=jnp.bfloat16,
                         int8_kv: bool = False):
        return transformer.init_paged_cache(self.cfg, batch, n_blocks,
                                            block_size, max_blocks_per_seq,
                                            dtype, int8_kv=int8_kv)

    def forward_step(self, params, tokens, pf_tokens, cache, n_valid,
                     pf_slot, pf_start, pf_valid, block_size: int,
                     backend: str = "naive"):
        """THE paged serving entry: one step of a slot-indexed generation
        group (decode and verify rows) and an optional prefill group in
        one pass (transformer.forward_step), behind
        serve.runner.ModelRunner."""
        with jax.default_matmul_precision(self._serving_precision):
            return transformer.forward_step(
                params, self.cfg, tokens, pf_tokens, cache, n_valid,
                pf_slot, pf_start, pf_valid, block_size, backend=backend)

    def decode_burst(self, params, cache, tables, tok0, lens0, alive0,
                     budget, stops, stop_len, hist0, sample_fn,
                     block_size: int, backend: str, k_ticks, k_max: int):
        """Device-resident K-tick decode loop for the async engine
        (docs/async.md): forward_step + sampling chained on device under
        one ``lax.while_loop`` with per-row early exit."""
        with jax.default_matmul_precision(self._serving_precision):
            return transformer.decode_burst(
                params, self.cfg, cache, tables, tok0, lens0, alive0,
                budget, stops, stop_len, hist0, sample_fn, block_size,
                backend, k_ticks, k_max)
