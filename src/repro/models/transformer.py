"""The decoder stack: pattern-unit scan over composable blocks.

A config's ``pattern_unit()`` (e.g. zamba2: 5x mamba2 + shared_attn) is the
scan body; the stack runs ``n_units`` copies with stacked per-unit params —
the Chipyard-style generator at the model level. Shared blocks (zamba2's
shared attention) live outside the scan and are closed over. Leading dense
layers (``first_k_dense_replace``, DeepSeek-V3 style) run before the scan,
their params and caches stacked under "lead" as the units' are under
"units".
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import (constrain_residual, constrain_seq_gathered,
                                 constrain_tp_exact)
from repro.models import attention, ffn, layers, moe, rope, ssm, xlstm


# ---------------------------------------------------------------------------
# Per-block init


def init_block(key, kind: str, cfg: ModelConfig, dtype,
               d_ff: Optional[int] = None):
    init_attn = attention.init_mla if cfg.mla else attention.init_attn
    if kind in ("attn", "shared_attn"):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"norm1": jnp.ones((cfg.d_model,), dtype),
                "attn": init_attn(k1, cfg, dtype),
                "norm2": jnp.ones((cfg.d_model,), dtype),
                "ffn": ffn.init_ffn(k2, cfg, dtype, d_ff=d_ff)}
    if kind == "moe":
        k1, k2 = jax.random.split(key)
        return {"norm1": jnp.ones((cfg.d_model,), dtype),
                "attn": init_attn(k1, cfg, dtype),
                "norm2": jnp.ones((cfg.d_model,), dtype),
                "moe": moe.init_moe(k2, cfg, dtype)}
    if kind == "mamba2":
        return {"norm": jnp.ones((cfg.d_model,), dtype),
                "mamba": ssm.init_mamba2(key, cfg, dtype)}
    if kind == "mlstm":
        return xlstm.init_mlstm_block(key, cfg, dtype)
    if kind == "slstm":
        return xlstm.init_slstm_block(key, cfg, dtype)
    raise ValueError(kind)


def init_block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype):
    if kind in ("attn", "shared_attn", "moe"):
        return attention.init_kv_cache(cfg, batch, max_len, dtype)
    if kind == "mamba2":
        return ssm.init_mamba2_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm.init_mlstm_cache(cfg, batch, dtype)
    if kind == "slstm":
        return xlstm.init_slstm_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Per-block forward / decode. ctx: dict(cos, sin, pos, shared_params)


def block_forward(kind, p, cfg: ModelConfig, x, ctx,
                  cache: Optional[dict]):
    if kind == "shared_attn":
        p = ctx["shared_params"]
    if kind in ("attn", "shared_attn", "moe"):
        h = constrain_seq_gathered(
            layers.rms_norm(x, p["norm1"], cfg.norm_eps))
        a, new_cache = attention.attn_forward(
            p["attn"], cfg, h, ctx["cos"], ctx["sin"], cache=cache,
            pos=ctx.get("pos"))
        x = x + a
        h = constrain_seq_gathered(
            layers.rms_norm(x, p["norm2"], cfg.norm_eps))
        if kind == "moe":
            y, aux = moe.moe_forward(p["moe"], cfg, h)
        else:
            y, aux = ffn.ffn_forward(p["ffn"], cfg, h), 0.0
            if ctx.get("decode_mask") is not None and cfg.relu_sparse:
                # teacher-forced scoring of served tokens: positions the
                # engine ran as decode rows took the sparse decode FFN
                y = jnp.where(ctx["decode_mask"][..., None],
                              ffn.ffn_decode(p["ffn"], cfg, h), y)
        return x + y, new_cache, aux
    if kind == "mamba2":
        h = constrain_seq_gathered(
            layers.rms_norm(x, p["norm"], cfg.norm_eps))
        y, new_cache = ssm.mamba2_forward(p["mamba"], cfg, h, cache=cache)
        return x + y, new_cache, 0.0
    if kind == "mlstm":
        y, new_cache = xlstm.mlstm_block_forward(
            p, cfg, constrain_seq_gathered(x), cache=cache)
        return y, new_cache, 0.0
    if kind == "slstm":
        y, new_cache = xlstm.slstm_block_forward(
            p, cfg, constrain_seq_gathered(x), cache=cache)
        return y, new_cache, 0.0
    raise ValueError(kind)


def block_step_paged(kind, p, cfg: ModelConfig, x, ctx, cache: dict):
    """ONE per-block body for every serving tick through block tables
    (the ModelRunner step). Only attention-family blocks carry a paged
    cache; recurrent blocks (O(1) state) have nothing to page.

    ctx["groups"] are the step's row groups (attention.attn_step_grouped);
    the FFN splits by group (ffn.ffn_step): the generation group's
    ctx["n_gen"] tokens come first in a packed x, and None means x holds
    the generation group alone.

    An MoE block takes the drop-free serving dispatch over the held
    experts (moe.moe_serve), padding positions (ctx["valid"] False)
    assigned nothing; its per-tick routing counts come back as the third
    value (None for other blocks).

    The ``jax.named_scope`` annotations ("attn", "ffn_dense",
    "ffn_sparse" inside ffn_step, "moe_router" / "moe_experts" /
    "ffn_dense" inside moe_serve, "logits" in _paged_stack) are the
    profiling contract (obs.costmodel): scope names survive into the
    compiled HLO op metadata, which is how per-scope FLOP/byte
    attribution in the roofline attainment report is computed. They add
    metadata only — the math (and greedy token streams) is unchanged."""
    if kind == "shared_attn":
        p = ctx["shared_params"]
    if kind in ("attn", "shared_attn", "moe"):
        # exact_tp pins (identity off-scope): the norm outputs stay
        # replicated so GSPMD can't back-propagate a d-sharded layout
        # into the norm's mean reduction — a psum whose accumulation
        # order would perturb the residual stream (and through int8 KV
        # quantization rounding, the emitted tokens)
        with jax.named_scope("attn"):
            h = constrain_tp_exact(layers.rms_norm(x, p["norm1"],
                                                   cfg.norm_eps))
            a, new_cache = attention.attn_step_grouped(
                p["attn"], cfg, h, ctx["cos"], ctx["sin"], cache,
                ctx["groups"], ctx["block_size"], backend=ctx["backend"])
            x = x + a
        h = constrain_tp_exact(layers.rms_norm(x, p["norm2"],
                                               cfg.norm_eps))
        stats = None
        if kind == "moe":
            y, stats = moe.moe_serve(p["moe"], cfg, h, ctx["valid"])
        else:
            y = ffn.ffn_step(p["ffn"], cfg, h, ctx["n_gen"])
        return x + y, new_cache, stats
    raise ValueError(f"paged step requires attention blocks, got {kind!r}")


def block_decode(kind, p, cfg: ModelConfig, x, ctx, cache: dict):
    if kind == "shared_attn":
        p = ctx["shared_params"]
    if kind in ("attn", "shared_attn", "moe"):
        h = layers.rms_norm(x, p["norm1"], cfg.norm_eps)
        a, new_cache = attention.attn_decode(
            p["attn"], cfg, h, ctx["cos"], ctx["sin"], cache, ctx["lens"])
        x = x + a
        h = layers.rms_norm(x, p["norm2"], cfg.norm_eps)
        if kind == "moe":
            y, _ = moe.moe_forward(p["moe"], cfg, h)
        else:
            y = ffn.ffn_decode(p["ffn"], cfg, h)
        return x + y, new_cache
    if kind == "mamba2":
        h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
        y, new_cache = ssm.mamba2_decode(p["mamba"], cfg, h, cache)
        return x + y, new_cache
    if kind == "mlstm":
        return xlstm.mlstm_block_decode(p, cfg, x, cache)
    if kind == "slstm":
        return xlstm.slstm_block_decode(p, cfg, x, cache)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Stack


def _lead_layers(params, cache, body, x):
    """Run the leading dense layers in order: ``body(x, (layer params,
    layer cache)) -> (x, new layer cache)``, each layer sliced out of the
    "lead" stack. Returns (x, the new "lead" stack, or None without a
    cache)."""
    n = jax.tree.leaves(params["lead"])[0].shape[0]
    outs = []
    for i in range(n):
        lc = None if cache is None else jax.tree.map(lambda a: a[i],
                                                      cache["lead"])
        x, nc = body(x, (jax.tree.map(lambda a: a[i], params["lead"]), lc))
        outs.append(nc)
    if cache is None:
        return x, None
    return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    unit = cfg.pattern_unit()
    n_units = cfg.n_units
    k_embed, k_head, k_units, k_shared = jax.random.split(key, 4)

    params: Dict[str, Any] = {}
    if cfg.n_codebooks:
        params["embed"] = layers.embed_init(
            k_embed, cfg.n_codebooks * cfg.vocab, cfg.d_model, dtype
        ).reshape(cfg.n_codebooks, cfg.vocab, cfg.d_model)
    else:
        params["embed"] = layers.embed_init(k_embed, cfg.vocab, cfg.d_model,
                                            dtype)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            params["head"] = layers.dense_init(
                k_head, (cfg.n_codebooks, cfg.d_model, cfg.vocab), dtype)
        else:
            params["head"] = layers.dense_init(
                k_head, (cfg.d_model, cfg.vocab), dtype)
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)

    def init_unit(k):
        ks = jax.random.split(k, len(unit))
        return {f"b{j}": init_block(ks[j], kind, cfg, dtype)
                for j, kind in enumerate(unit)
                if kind != "shared_attn"}

    unit_keys = jax.random.split(k_units, n_units)
    params["units"] = jax.vmap(init_unit)(unit_keys)
    if cfg.first_k_dense_replace:
        params["lead"] = jax.vmap(lambda k: {"b0": init_block(
            k, "attn", cfg, dtype, d_ff=cfg.dense_d_ff or None)})(
            jax.random.split(jax.random.fold_in(k_units, 1),
                             cfg.first_k_dense_replace))
    if "shared_attn" in unit:
        params["shared"] = init_block(k_shared, "shared_attn", cfg, dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    unit = cfg.pattern_unit()

    def one_unit():
        return {f"b{j}": init_block_cache(kind, cfg, batch, max_len, dtype)
                for j, kind in enumerate(unit)}

    units = [one_unit() for _ in range(cfg.n_units)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *units)
    # per-row context lengths (continuous batching: slots advance
    # independently)
    out = {"lens": jnp.zeros((batch,), jnp.int32), "units": stacked}
    if cfg.first_k_dense_replace:
        out["lead"] = jax.tree.map(
            lambda a: jnp.stack([a] * cfg.first_k_dense_replace),
            {"b0": init_block_cache("attn", cfg, batch, max_len, dtype)})
    return out


def init_paged_cache(cfg: ModelConfig, batch: int, n_blocks: int,
                     block_size: int, max_blocks_per_seq: int, dtype,
                     int8_kv: bool = False):
    """Paged decode cache: one shared block pool per attention layer plus
    per-slot block tables (sentinel-filled; serve.paged_kv assigns blocks).
    Requires an attention-only pattern — recurrent blocks keep O(1) state
    and are served through the contiguous engine instead."""
    unit = cfg.pattern_unit()
    bad = [k for k in unit if k not in ("attn", "shared_attn", "moe")]
    if bad:
        raise ValueError(
            f"{cfg.name}: paged KV needs an attention-only pattern "
            f"(found {bad}); serve this family with ServeConfig(paged=False)")

    def pools(n: int, kinds):
        if cfg.mla:
            # latent leaves made at their stacked shape, with no
            # per-layer copies to stack
            leaf = jax.eval_shape(lambda: attention.init_paged_kv_cache(
                cfg, n_blocks, block_size, dtype, int8_kv=int8_kv))["kv"]
            return {f"b{j}": {"kv": jnp.zeros((n,) + leaf.shape,
                                              leaf.dtype)}
                    for j in range(len(kinds))}
        units = [{f"b{j}": attention.init_paged_kv_cache(
                    cfg, n_blocks, block_size, dtype, int8_kv=int8_kv)
                  for j in range(len(kinds))} for _ in range(n)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *units)

    tables = jnp.full((batch, max_blocks_per_seq), n_blocks, jnp.int32)
    out = {"lens": jnp.zeros((batch,), jnp.int32), "block_tables": tables,
           "units": pools(cfg.n_units, unit)}
    if cfg.first_k_dense_replace:
        out["lead"] = pools(cfg.first_k_dense_replace, ("attn",))
    return out


def _embed_inputs(params, cfg: ModelConfig, batch: dict):
    tokens = batch["tokens"]
    if cfg.n_codebooks:
        # tokens: [B, S, nc] -> sum of per-codebook embeddings (gathered
        # from the flattened [nc*V, d] table, then reduced over nc)
        nc, V, d = params["embed"].shape
        flat = params["embed"].reshape(nc * V, d)
        idx = tokens + (jnp.arange(nc) * V)[None, None, :]
        x = jnp.sum(jnp.take(flat, idx, axis=0), axis=2)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend == "vision_stub" and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(x.dtype)
        pad = x.shape[1] - ve.shape[1]
        x = x + jnp.pad(ve, ((0, 0), (0, pad), (0, 0)))
    if cfg.frontend == "audio_stub" and "audio_embeds" in batch:
        ae = batch["audio_embeds"].astype(x.dtype)
        pad = x.shape[1] - ae.shape[1]
        x = x + jnp.pad(ae, ((0, 0), (0, pad), (0, 0)))
    return x


def _positions(cfg: ModelConfig, batch: dict, B: int, S: int,
               offset=0):
    if cfg.mrope:
        if "mrope_positions" in batch:
            return batch["mrope_positions"]
        # text-only M-RoPE default: all 3 channels share the position
        pos = jnp.arange(S)[None, None, :] + offset
        return jnp.broadcast_to(pos, (3, B, S))
    pos = jnp.arange(S)[None, :] + offset
    return jnp.broadcast_to(pos, (B, S))


def _rope_tables(cfg: ModelConfig, positions):
    if cfg.pos_emb != "rope":
        # identity rotation
        if cfg.mrope:
            positions = positions[0]
        B, S = positions.shape
        return (jnp.ones((B, S, cfg.rope_dim // 2), jnp.float32),
                jnp.zeros((B, S, cfg.rope_dim // 2), jnp.float32))
    if cfg.mrope:
        return rope.mrope_cos_sin(positions, cfg.d_head, cfg.rope_theta,
                                  sections=_mrope_sections(cfg))
    return rope.rope_cos_sin(positions, cfg.rope_dim, cfg.rope_theta)


def _mrope_sections(cfg: ModelConfig):
    half = cfg.d_head // 2
    t = half // 4
    rest = half - t
    h = rest // 2
    return (t, h, rest - h)


def forward(params, cfg: ModelConfig, batch: dict, cache=None):
    """Full-sequence forward (train / prefill).

    ``batch["decode_from"]`` (optional, i32[B]) teacher-forces a served
    continuation: positions >= decode_from[b] are the ones the serving
    engine fed as decode rows, and take the decode FFN (the sparse
    gather under ``relu_sparse``) instead of the dense one.

    Returns (hidden [B,S,d], aux_loss, new_cache)."""
    x = _embed_inputs(params, cfg, batch)
    x = constrain_residual(x)
    B, S, _ = x.shape
    start = 0  # prefill always fills [0, S); per-slot merge is engine-side
    positions = _positions(cfg, batch, B, S, offset=start)
    cos, sin = _rope_tables(cfg, positions)
    if cfg.pos_emb == "sin":
        p1 = positions[0] if cfg.mrope else positions
        x = x + layers.sinusoidal_positions(p1, cfg.d_model).astype(x.dtype)

    ctx = {"cos": cos, "sin": sin, "pos": start,
           "shared_params": params.get("shared")}
    if "decode_from" in batch:
        ctx["decode_mask"] = (jnp.arange(S)[None, :]
                              >= batch["decode_from"][:, None])
    unit = cfg.pattern_unit()
    new_lead = None
    if "lead" in params:
        def lead_body(x, xs):
            bp, bc = xs
            x, nc, _ = block_forward("attn", bp["b0"], cfg, x, ctx,
                                     None if bc is None else bc["b0"])
            return constrain_residual(x), {"b0": nc}
        x, new_lead = _lead_layers(params, cache, lead_body, x)

    def unit_body(carry, xs):
        x, aux = carry
        unit_p, unit_cache = xs
        new_caches = {}
        for j, kind in enumerate(unit):
            bp = unit_p.get(f"b{j}")
            bc = unit_cache[f"b{j}"] if unit_cache is not None else None
            x, nc, a = block_forward(kind, bp, cfg, x, ctx, bc)
            x = constrain_residual(x)
            new_caches[f"b{j}"] = nc
            aux = aux + a
        return (x, aux), (new_caches if unit_cache is not None else 0)

    body = unit_body
    if cfg.remat:
        body = jax.checkpoint(unit_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    if cfg.unroll:
        # loop-free lowering for the dry-run cost probes
        carry = (x, jnp.zeros((), jnp.float32))
        new_unit_list = []
        for i in range(cfg.n_units):
            u_p = jax.tree.map(lambda a: a[i], params["units"])
            u_c = (jax.tree.map(lambda a: a[i], cache["units"])
                   if cache is not None else None)
            carry, ys = body(carry, (u_p, u_c))
            new_unit_list.append(ys)
        (x, aux) = carry
        new_cache = None
        if cache is not None:
            new_units = jax.tree.map(lambda *xs: jnp.stack(xs),
                                     *new_unit_list)
            new_cache = {"lens": jnp.full_like(cache["lens"], S),
                         "units": new_units}
    elif cache is None:
        (x, aux), _ = jax.lax.scan(
            lambda c, p: (body(c, (p, None))[0], None),
            (x, jnp.zeros((), jnp.float32)), params["units"])
        new_cache = None
    else:
        (x, aux), new_units = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["units"], cache["units"]))
        new_cache = {"lens": jnp.full_like(cache["lens"], S),
                     "units": new_units}
    if new_lead is not None:
        new_cache["lead"] = new_lead

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, new_cache


def decode_step(params, cfg: ModelConfig, tokens, cache, batch_extra=None):
    """One-token decode. tokens: [B, 1] (or [B, 1, nc]).
    Per-row positions come from cache['lens']. Returns (logits, new_cache)."""
    batch = {"tokens": tokens}
    if batch_extra:
        batch.update(batch_extra)
    x = _embed_inputs(params, cfg, batch)
    B = x.shape[0]
    lens = cache["lens"]
    positions = lens[:, None] if not cfg.mrope \
        else jnp.broadcast_to(lens[None, :, None], (3, B, 1))
    cos, sin = _rope_tables(cfg, positions)
    if cfg.pos_emb == "sin":
        p1 = positions[0] if cfg.mrope else positions
        x = x + layers.sinusoidal_positions(p1, cfg.d_model).astype(x.dtype)

    ctx = {"cos": cos, "sin": sin, "lens": lens,
           "shared_params": params.get("shared")}
    unit = cfg.pattern_unit()
    new_lead = None
    if "lead" in params:
        def lead_body(x, xs):
            bp, bc = xs
            x, nc = block_decode("attn", bp["b0"], cfg, x, ctx, bc["b0"])
            return constrain_residual(x), {"b0": nc}
        x, new_lead = _lead_layers(params, cache, lead_body, x)

    def unit_body(x, xs):
        unit_p, unit_cache = xs
        new_caches = {}
        for j, kind in enumerate(unit):
            bp = unit_p.get(f"b{j}")
            x, nc = block_decode(kind, bp, cfg, x, ctx, unit_cache[f"b{j}"])
            x = constrain_residual(x)
            new_caches[f"b{j}"] = nc
        return x, new_caches

    if cfg.unroll:
        new_unit_list = []
        for i in range(cfg.n_units):
            u_p = jax.tree.map(lambda a: a[i], params["units"])
            u_c = jax.tree.map(lambda a: a[i], cache["units"])
            x, ys = unit_body(x, (u_p, u_c))
            new_unit_list.append(ys)
        new_units = jax.tree.map(lambda *xs: jnp.stack(xs), *new_unit_list)
    else:
        x, new_units = jax.lax.scan(unit_body, x,
                                    (params["units"], cache["units"]))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(params, cfg, x)
    new_cache = {"lens": lens + 1, "units": new_units}
    if new_lead is not None:
        new_cache["lead"] = new_lead
    return logits, new_cache


def forward_step(params, cfg: ModelConfig, tokens, pf_tokens, cache,
                 n_valid, pf_slot, pf_start, pf_valid, block_size: int,
                 backend: str = "naive"):
    """THE serving entry point: one fixed-shape step through block tables
    (the ModelRunner contract), as two row groups in ONE pass over the
    layers.

      * generation group: tokens [B, W], row b at slot b, at positions
        cache["lens"][b] + j, n_valid[b] of them real (0 for every slot
        that is idle here: sentinel tables, nothing written). W is 1 for
        decode rows and K+1 for verify rows;
      * prefill group (absent when ``pf_tokens`` is None; the pf_*
        arguments are then None too): pf_tokens [P, C], row i for slot
        pf_slot[i] (an id >= B pads the group: sentinel tables, nothing
        written) at positions pf_start[i] + j, pf_valid[i] of them real.

    Every position's logits come back: logits[b, j] is the model's
    distribution for the token FOLLOWING tokens[b, j]. So a decode row
    reads its next-token logits at j = 0, a verify row the whole [0, K]
    chain (the engine commits the accepted prefix host-side: ``lens``
    never advances on device, only the engine knows how much of a row
    committed, so it republishes lens and tables before every step), and
    a prefill row that finished its prompt reads first-token logits at
    j = pf_valid[i] - 1.

    With a prefill group, norms, projections and the FFN run once on the
    B*W + P*C tokens packed together; attention runs per group at the
    group's width; both groups' KV lands in the pool in one store. The
    generation group takes the sparse decode FFN, the prefill group the
    dense one (ffn.ffn_step); without a prefill group the residual stream
    stays [B, W, d] and no dense down-projection is traced. ``backend``
    selects the attention read path ("naive" | "flash", see
    attention.attn_step_grouped).

    Returns (generation logits [B, W, V], prefill logits [P, C, V] or
    None — with an nc axis before V for codebook models — the updated
    cache, and the step's routing counts: None for a model without MoE
    layers, see ``_paged_stack``).
    """
    B, W = tokens.shape[0], tokens.shape[1]
    x, cos, sin = _step_inputs(params, cfg, tokens, cache["lens"])
    n_blocks = jax.tree.leaves(cache["units"])[0].shape[1]
    tables = cache["block_tables"]
    groups = [attention.PagedGroup(
        cache["lens"], n_valid,
        jnp.where(n_valid[:, None] > 0, tables, n_blocks), W)]
    n_gen = None
    if pf_tokens is not None:
        P, C = pf_tokens.shape[0], pf_tokens.shape[1]
        xp, cp, sp = _step_inputs(params, cfg, pf_tokens, pf_start)
        pf_tables = jnp.take(tables, pf_slot, axis=0, mode="fill",
                             fill_value=n_blocks)
        groups.append(attention.PagedGroup(
            pf_start, pf_valid,
            jnp.where(pf_valid[:, None] > 0, pf_tables, n_blocks), C))
        x, cos, sin = _pack(x, xp), _pack(cos, cp), _pack(sin, sp)
        n_gen = B * W
    ctx = {"cos": cos, "sin": sin, "groups": tuple(groups), "n_gen": n_gen,
           "block_size": block_size, "backend": backend,
           "shared_params": params.get("shared")}
    if "moe" in cfg.pattern_unit():
        valid = jnp.arange(W)[None, :] < n_valid[:, None]
        if n_gen is not None:
            valid = jnp.concatenate([
                valid.reshape(-1),
                (jnp.arange(C)[None, :] < pf_valid[:, None]).reshape(-1)
            ])[None]
        ctx["valid"] = valid
    logits, cache, counts = _paged_stack(params, cfg, x, cache, ctx)
    if n_gen is None:
        return logits, None, cache, counts
    return (logits[0, :n_gen].reshape((B, W) + logits.shape[2:]),
            logits[0, n_gen:].reshape((P, C) + logits.shape[2:]), cache,
            counts)


def _pack(a, b):
    """Two groups' [rows, width, ...] arrays as one [1, N, ...], row-major
    and in order."""
    return jnp.concatenate([a.reshape((1, -1) + a.shape[2:]),
                            b.reshape((1, -1) + b.shape[2:])], axis=1)


def _step_inputs(params, cfg: ModelConfig, tokens, lens):
    """Embedded tokens [B, S, d] of rows starting at positions ``lens``,
    and their rotary tables."""
    # exact_tp: the embedding gather lands d-sharded (the table's output
    # dim is partitioned); gather it back to replicated — an exact
    # concatenation — before the residual stream starts
    x = constrain_tp_exact(_embed_inputs(params, cfg, {"tokens": tokens}))
    B, S = x.shape[0], x.shape[1]
    positions = lens[:, None] + jnp.arange(S)[None, :]
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[None], (3, B, S))
    cos, sin = _rope_tables(cfg, positions)
    if cfg.pos_emb == "sin":
        p1 = positions[0] if cfg.mrope else positions
        x = x + layers.sinusoidal_positions(p1, cfg.d_model).astype(x.dtype)
    return x, cos, sin


def _paged_stack(params, cfg: ModelConfig, x, cache, ctx):
    """The layer stack of a paged step over residual ``x`` and its final
    logits; returns (logits, the updated cache, counts). ``counts`` is
    None for a model without MoE layers, else i32[3], this step's
    routing counts over its layers: held assignments and assignments
    held elsewhere (summed), and the most tokens on one held expert of
    one layer."""
    unit = cfg.pattern_unit()
    new_cache = {"lens": cache["lens"],
                 "block_tables": cache["block_tables"]}
    if "lead" in params:
        def lead_body(x, xs):
            bp, bc = xs
            x, nc, _ = block_step_paged("attn", bp["b0"], cfg, x, ctx,
                                        bc["b0"])
            return constrain_residual(x), {"b0": nc}
        x, new_cache["lead"] = _lead_layers(params, cache, lead_body, x)

    def unit_body(x, xs):
        unit_p, unit_cache = xs
        new_caches, stats = {}, []
        for j, kind in enumerate(unit):
            bp = unit_p.get(f"b{j}")
            x, nc, st = block_step_paged(kind, bp, cfg, x, ctx,
                                         unit_cache[f"b{j}"])
            x = constrain_residual(x)
            new_caches[f"b{j}"] = nc
            if st is not None:
                stats.append(st)
        return x, (new_caches, jnp.stack(stats) if stats else None)

    if cfg.unroll:
        # loop-free twin of the scan below (same math, same cache
        # layout). obs.costmodel lowers the step with unroll=True so
        # compiled.cost_analysis() and the HLO-text scope attribution
        # count every unit — XLA reports a while-loop body ONCE
        # regardless of trip count, which would undercount the stack
        # n_units-fold.
        new_unit_list = []
        for i in range(cfg.n_units):
            u_p = jax.tree.map(lambda a: a[i], params["units"])
            u_c = jax.tree.map(lambda a: a[i], cache["units"])
            x, nc = unit_body(x, (u_p, u_c))
            new_unit_list.append(nc)
        new_units, stats = jax.tree.map(lambda *xs: jnp.stack(xs),
                                        *new_unit_list)
    else:
        x, (new_units, stats) = jax.lax.scan(
            unit_body, x, (params["units"], cache["units"]))
    new_cache["units"] = new_units
    counts = None
    if stats is not None:
        st = stats.reshape(-1, 3)
        counts = jnp.stack(
            [jnp.sum(st[:, 0]), jnp.sum(st[:, 1]), jnp.max(st[:, 2])])
    with jax.named_scope("logits"):
        x = constrain_tp_exact(
            layers.rms_norm(x, params["final_norm"], cfg.norm_eps))
        logits = project_logits(params, cfg, x)
    return logits, new_cache, counts


def decode_burst(params, cfg: ModelConfig, cache, tables, tok0, lens0,
                 alive0, budget, stops, stop_len, hist0, sample_fn,
                 block_size: int, backend: str, k_ticks, k_max: int):
    """Device-resident decode loop: up to ``k_ticks`` single-token decode
    steps inside one ``lax.while_loop``, feeding each sampled token back
    as the next step's input without a host round-trip (docs/async.md).

    Per-row early exit is carried on device: row b goes dead (``alive=0``,
    masking its KV writes exactly like an IDLE runner row) once it has
    emitted ``budget[b]`` tokens or its generated-stream suffix matches a
    stop sequence. ``stops`` is i32[B, NS, L] right-aligned (-1 padded)
    with per-stop lengths ``stop_len`` i32[B, NS] (0 = unused row);
    ``hist0`` i32[B, L] seeds the suffix ring with the last L tokens
    already generated, so stops spanning the burst boundary still match.
    Stops longer than L are matched host-side after the burst — the
    engine discards any overrun (identity is preserved either way, the
    device match only buys the early exit).

    ``sample_fn(last_logits, i) -> (tok i32[B], lp f32[B])`` is injected
    by the runner (serve.sampling stays out of the model layer); ``i`` is
    the traced burst index, used to select the per-draw PRNG key.
    ``k_ticks`` is a traced bound (one compilation serves any burst
    length up to the static ``k_max``, the emitted-buffer width).

    Returns (emitted i32[B, k_max] — -1 past each row's last live step,
    logprobs f32[B, k_max], new_cache, final lens, n_emitted i32[B],
    counts): ``counts`` is None for a model without MoE layers, else the
    routing counts of ``_paged_stack`` over every step of the burst
    (assignments summed, the largest load kept).
    The loop never advances the engine's committed state: the host
    replays ``emitted`` through the exact synchronous commit path, which
    is what keeps greedy output token-identical to the per-tick engine.
    """
    B = tok0.shape[0]
    L = hist0.shape[1]
    moe = "moe" in cfg.pattern_unit()
    col_ids = jnp.arange(k_max)[None, :]
    pos_mask = jnp.arange(L)[None, None, :] < (L - stop_len[:, :, None])

    def cond(c):
        return (c["i"] < k_ticks) & jnp.any(c["alive"] > 0)

    def body(c):
        cache = dict(c["cache"])
        cache["lens"] = c["lens"]
        cache["block_tables"] = tables
        logits, _, cache, counts = forward_step(
            params, cfg, c["tok"][:, None], None, cache, c["alive"], None,
            None, None, block_size, backend=backend)
        if moe:
            counts = jnp.concatenate([c["counts"][:2] + counts[:2],
                                      jnp.maximum(c["counts"][2:],
                                                  counts[2:])])
        last = logits[:, 0].astype(jnp.float32)
        ntok, nlp = sample_fn(last, c["i"])
        ntok = ntok.astype(jnp.int32)
        live = c["alive"] > 0
        col = (col_ids == c["i"]) & live[:, None]
        emitted = jnp.where(col, ntok[:, None], c["emitted"])
        lp = jnp.where(col, nlp[:, None], c["lp"])
        hist = jnp.where(
            live[:, None],
            jnp.concatenate([c["hist"][:, 1:], ntok[:, None]], axis=1),
            c["hist"])
        n_emit = c["n_emit"] + live.astype(jnp.int32)
        matched = jnp.any(
            jnp.all(pos_mask | (hist[:, None, :] == stops), axis=-1)
            & (stop_len > 0), axis=-1)
        alive = jnp.where(live & ~matched & (n_emit < budget),
                          1, 0).astype(jnp.int32)
        return {"cache": cache,
                "tok": jnp.where(live, ntok, c["tok"]),
                "lens": c["lens"] + live.astype(c["lens"].dtype),
                "hist": hist, "emitted": emitted, "lp": lp,
                "alive": alive, "n_emit": n_emit, "i": c["i"] + 1,
                "counts": counts}

    init = {"cache": cache, "tok": tok0, "lens": lens0, "hist": hist0,
            "emitted": jnp.full((B, k_max), -1, jnp.int32),
            "lp": jnp.zeros((B, k_max), jnp.float32),
            "alive": alive0, "n_emit": jnp.zeros((B,), jnp.int32),
            "i": jnp.asarray(0, jnp.int32),
            "counts": jnp.zeros((3,), jnp.int32) if moe else None}
    fin = jax.lax.while_loop(cond, body, init)
    new_cache = dict(fin["cache"])
    new_cache["lens"] = fin["lens"]
    return (fin["emitted"], fin["lp"], new_cache, fin["lens"],
            fin["n_emit"], fin["counts"])


def project_logits(params, cfg: ModelConfig, x):
    """x: [B, S, d] -> logits (fp32 via accumulate-in-f32 dots; operands
    stay bf16 so XLA never materializes an f32 copy of the vocab matrix).
    Musicgen: [B, S, nc, V]."""
    if cfg.n_codebooks:
        head = params["head"]  # [nc, d, V]
        return jnp.einsum("bsd,ndv->bsnv", x, head,
                          preferred_element_type=jnp.float32)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"],
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bsd,dv->bsv", x, params["head"],
                      preferred_element_type=jnp.float32)


def loss_fn(params, cfg: ModelConfig, batch: dict, token_chunk: int = 0):
    """Next-token CE (+ MoE aux). Chunked over tokens so the [*, V] logits
    never materialize for the full sequence (vocab up to 202k)."""
    hidden, aux, _ = forward(params, cfg, batch)
    labels = batch["labels"]
    mask = batch.get("mask")
    B, S, d = hidden.shape

    if token_chunk <= 0:
        # pick a chunk so logits stay ~<=256 MiB fp32 per device pre-shard
        token_chunk = max(1, min(S, int(2 ** 26 // max(cfg.vocab, 1)) or 1))
    n_chunks = max(1, S // token_chunk)
    while S % n_chunks:
        n_chunks -= 1
    tc = S // n_chunks

    hid = hidden.reshape(B, n_chunks, tc, d).swapaxes(0, 1)
    lab = labels.reshape((B, n_chunks, tc) + labels.shape[2:]).swapaxes(0, 1)
    if mask is not None:
        msk = mask.reshape(B, n_chunks, tc).swapaxes(0, 1)
    else:
        msk = jnp.ones((n_chunks, B, tc), jnp.float32)

    def chunk_loss(_, xs):
        h, y, m = xs
        logits = project_logits(params, cfg, constrain_seq_gathered(h))
        if cfg.n_codebooks:
            m = m[..., None] * jnp.ones(cfg.n_codebooks)
        ce = layers.softmax_cross_entropy(logits, y, m)
        return 0.0, (ce, jnp.sum(m))

    chunk = jax.checkpoint(chunk_loss,
                           policy=jax.checkpoint_policies.nothing_saveable)
    _, (ces, ws) = jax.lax.scan(chunk, 0.0, (hid, lab, msk),
                                unroll=cfg.unroll)
    total_w = jnp.maximum(jnp.sum(ws), 1.0)
    ce = jnp.sum(ces * ws) / total_w
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux, "ppl": jnp.exp(ce)}
