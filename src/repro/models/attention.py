"""Multi-head attention (MHA/GQA/MQA) with KV cache, qk-norm, qkv-bias,
and latent attention (MLA, DeepSeek-V2/V3) over a cache of one latent per
token."""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain_tp_exact
from repro.models import layers, rope
from repro.models.flash import (NEG_INF, _gqa_out, _gqa_scores,
                                block_causal_attention,
                                blockwise_attention,
                                reference_attention)


def init_attn(key, cfg: ModelConfig, dtype):
    d, dh = cfg.d_model, cfg.d_head
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, nq * dh), dtype),
        "wk": layers.dense_init(ks[1], (d, nkv * dh), dtype),
        "wv": layers.dense_init(ks[2], (d, nkv * dh), dtype),
        "wo": layers.dense_init(ks[3], (nq * dh, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * dh,), dtype)
        p["bk"] = jnp.zeros((nkv * dh,), dtype)
        p["bv"] = jnp.zeros((nkv * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    if cfg.mla:
        return {"kv": jnp.zeros((batch, max_len, 1, latent_width(cfg)),
                                dtype)}
    shape = (batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                        dtype, int8_kv: bool = False):
    """Block-pool KV storage: requests own scattered fixed-size token
    blocks instead of a contiguous [B, max_seq] row (vLLM-style paged
    attention). Block index ``n_blocks`` is the invalid sentinel — writes
    through it drop, reads through it fill zeros.

    ``int8_kv``: store 1 byte/element plus one f32 scale per (token, head)
    for each of K and V (kv_cache.quantize_kv layout) — halves the decode
    KV stream on top of the paper's weight-side savings. Byte accounting
    in serve.paged_kv.kv_bytes_per_token matches this layout exactly.

    Latent attention (``cfg.mla``) keeps one leaf ``kv`` [n_blocks, bs,
    1, kv_lora_rank + qk_rope_head_dim]: per token the RMS-normed latent
    and the roped key its heads share, read like one KV head of MQA."""
    if cfg.mla:
        if int8_kv:
            raise ValueError(f"{cfg.name}: the latent pool is float; "
                             "int8 KV (kv_quant) covers k/v pools only")
        return {"kv": jnp.zeros((n_blocks, block_size, 1,
                                 latent_width(cfg)), dtype)}
    shape = (n_blocks, block_size, cfg.n_kv_heads, cfg.d_head)
    if not int8_kv:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    sshape = shape[:-1] + (1,)
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32)}


def _qkv(p, cfg: ModelConfig, x):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = layers.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_forward(p, cfg: ModelConfig, x, cos, sin,
                 cache: Optional[dict] = None,
                 pos: Optional[jax.Array] = None,
                 block_kv: int = 512):
    """Attention for train/prefill (full sequence, causal).

    x: [B, S, d]; cos/sin: [B, S, dh//2]. If ``cache`` is given, writes
    K/V at [pos, pos+S) and returns (out, new_cache); attends only within
    the current segment (prefill semantics: segment starts at pos=0).
    """
    B, S, _ = x.shape
    if cfg.mla:
        q, kv = _mla_qkv(p, cfg, x, cos, sin)
        new_cache = None
        if cache is not None:
            new_cache = {"kv": jax.lax.dynamic_update_slice_in_dim(
                cache["kv"], kv.astype(cache["kv"].dtype),
                0 if pos is None else pos, axis=1)}
        gpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return _mla_out(p, cfg, _mla_attend(cfg, q, kv, gpos),
                        x.dtype), new_cache
    q, k, v = _qkv(p, cfg, x)
    q = rope.apply_rope(q, cos, sin)
    k = rope.apply_rope(k, cos, sin)

    new_cache = None
    if cache is not None:
        start = 0 if pos is None else pos
        new_cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"],
                                                     k.astype(cache["k"].dtype),
                                                     start, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"],
                                                     v.astype(cache["v"].dtype),
                                                     start, axis=1),
        }

    qg = q.reshape(B, S, cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head)
    if cfg.block_causal and S > block_kv:
        o = block_causal_attention(qg, k, v, block_q=block_kv,
                                   block_kv=block_kv, unroll=cfg.unroll)
    else:
        o = blockwise_attention(qg, k, v, causal=True, block_kv=block_kv,
                                unroll=cfg.unroll)
    o = o.reshape(B, S, cfg.n_heads * cfg.d_head)
    out = o @ p["wo"]
    return (out, new_cache) if cache is not None else (out, None)


def attn_decode(p, cfg: ModelConfig, x, cos, sin, cache: dict,
                lens: jax.Array, block_kv: int = 1024):
    """One-token decode: x [B, 1, d]; ``lens`` i32[B] is each row's current
    context length — the new KV is scattered at position lens[b] (per-slot
    continuous batching) and attention masks to lens+1.

    The KV cache may be sequence-sharded across the model axis — GSPMD
    handles the baseline; the optimized distributed-LSE path lives in
    ``repro.dist.collectives``.
    """
    B = x.shape[0]
    rows = jnp.arange(B)
    if cfg.mla:
        q, kv = _mla_qkv(p, cfg, x, cos, sin)
        ckv = cache["kv"].at[rows, lens].set(
            kv[:, 0].astype(cache["kv"].dtype), mode="drop")
        o = _mla_attend(cfg, q, ckv, lens[:, None])
        return _mla_out(p, cfg, o, x.dtype), {"kv": ckv}
    q, k, v = _qkv(p, cfg, x)
    q = rope.apply_rope(q, cos, sin)
    k = rope.apply_rope(k, cos, sin)
    ck = cache["k"].at[rows, lens].set(k[:, 0].astype(cache["k"].dtype),
                                       mode="drop")
    cv = cache["v"].at[rows, lens].set(v[:, 0].astype(cache["v"].dtype),
                                       mode="drop")
    qg = q.reshape(B, 1, cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head)
    # Single-token decode uses FULL-score attention (no KV-block scan):
    # scores are [B, Kv, G, 1, S] — small — and, crucially, GSPMD shards
    # the softmax reduction over the seq-sharded KV cache cleanly (the
    # scan's dynamic-slice forces involuntary resharding). The Pallas
    # flash-decode kernel covers the on-chip version (kernels/decode_attn).
    o = reference_attention(qg, ck, cv, causal=False, kv_len=lens + 1)
    o = o.reshape(B, 1, cfg.n_heads * cfg.d_head)
    return o @ p["wo"], {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Paged attention: decode + chunked prefill read/write through block tables


def _kv_seq_scope(seq_len: int):
    """The active mesh IF single-token paged decode should route through
    the LSE-combine collective: an activation_sharding_scope with
    ``shard_kv_seq`` is active, the mesh has a real 'model' axis, and the
    gathered logical sequence divides across it. Returns None otherwise
    (the replicated/head-sharded reference path)."""
    from repro.dist import sharding
    scope = sharding.current_scope()
    if scope is None or not scope[1].shard_kv_seq:
        return None
    mesh = scope[0]
    if "model" not in mesh.axis_names:
        return None
    msize = mesh.shape["model"]
    if msize <= 1 or seq_len % msize != 0:
        return None
    return mesh


def _gather_paged(cache_leaf, tables, n_blocks: int):
    """[n_blocks, bs, Kv, Dh] gathered via tables i32[B, MB] ->
    [B, MB*bs, Kv, Dh]. Sentinel entries (== n_blocks) fill zeros; those
    positions are >= kv_len and masked out of the softmax anyway."""
    B, MB = tables.shape
    bs = cache_leaf.shape[1]
    g = jnp.take(cache_leaf, tables.reshape(-1), axis=0, mode="fill",
                 fill_value=0)
    return g.reshape(B, MB * bs, *cache_leaf.shape[2:])


def _store_paged(cache: dict, name: str, blk, off, val):
    """Scatter ``val`` into pool leaf ``name`` at (blk, off); sentinel
    indices drop. int8 pools (leaf has a ``{name}_scale`` sibling) route
    through kv_cache.quantize_kv: 1 byte/element + f32 per-(token, head)
    scales. Returns the updated leaves as a dict fragment."""
    if f"{name}_scale" not in cache:
        return {name: cache[name].at[blk, off].set(
            val.astype(cache[name].dtype), mode="drop")}
    from repro.serve.kv_cache import quantize_kv  # lazy: avoids cycle
    (q8, scale), _ = quantize_kv(val, val)
    return {name: cache[name].at[blk, off].set(q8, mode="drop"),
            f"{name}_scale": cache[f"{name}_scale"].at[blk, off].set(
                scale, mode="drop")}


def _read_paged(cache: dict, name: str, tables, n_blocks: int):
    """Gather pool leaf ``name`` through block tables, dequantizing int8
    pools back to f32 (sentinel blocks gather zero scales -> zeros)."""
    g = _gather_paged(cache[name], tables, n_blocks)
    if f"{name}_scale" not in cache:
        return g
    s = _gather_paged(cache[f"{name}_scale"], tables, n_blocks)
    return g.astype(jnp.float32) * s


def _pool_leaf(cache: dict, name: str):
    """Pool leaf ``name`` whole, int8 pools dequantized to f32 the way
    _read_paged dequantizes what it gathers."""
    if f"{name}_scale" not in cache:
        return cache[name]
    return cache[name].astype(jnp.float32) * cache[f"{name}_scale"]


def reads_pool(cfg: ModelConfig, rows: int, table_width: int,
               n_blocks: int, block_size: int, backend: str) -> bool:
    """Whether a width-1 group of ``rows`` rows, each with a block table
    of ``table_width`` columns, attends over the whole pool leaf
    (_attend_pool) instead of gathering its tables: when the tables
    span more blocks than the pool holds. The gather stays for the flash
    kernel, for latent attention, and under a kv-seq mesh scope (the
    LSE-combine path shards the gathered sequence). Static shapes only,
    so the runner can tell which read a step executable takes."""
    return (backend != "flash" and not cfg.mla
            and rows * table_width > n_blocks
            and _kv_seq_scope(table_width * block_size) is None)


def _attend_pool(cfg: ModelConfig, qg, cache: dict, tables, lens,
                 block_size: int):
    """Single-token attention of qg [B, Kv, G, Dh] over every slot of the
    pool: slot (blk, off) sits at position c * bs + off of row b when blk
    is column c of tables[b], and is visible iff that position is below
    lens[b] + 1, the gather path's kv_len. The map is per row, so a block
    two rows share is visible to both. The pool enters the einsums with no
    row axis: each layer reads it once for all rows, where the gather
    reads rows x table columns blocks, most of them sentinels. Scores,
    softmax and the output dot are reference_attention's. An all-sentinel
    row sees nothing and gets a finite, discarded output. Returns o
    [B, Kv, G, Dh] in qg's dtype."""
    B, MB = tables.shape
    k = _pool_leaf(cache, "k")
    v = _pool_leaf(cache, "v")
    n_blocks = k.shape[0]
    # col[b, blk]: blk's column in row b's table; MB where the row holds
    # no such block, which puts its slots past any row's kv_len
    # (sentinel entries are out of range and drop)
    col = jnp.full((B, n_blocks), MB, jnp.int32).at[
        jnp.arange(B)[:, None], tables].set(
        jnp.broadcast_to(jnp.arange(MB, dtype=jnp.int32), (B, MB)),
        mode="drop")
    pos = col[:, :, None] * block_size + jnp.arange(block_size)
    vis = pos < (lens + 1)[:, None, None]
    kf = k.reshape((n_blocks * block_size,) + k.shape[2:])
    vf = v.reshape(kf.shape)
    s = jnp.einsum("bkgd,nkd->bkgn",
                   qg * jnp.asarray(cfg.d_head ** -0.5, qg.dtype), kf,
                   preferred_element_type=jnp.float32)
    s = jnp.where(vis.reshape(B, 1, 1, -1), s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgn,nkd->bkgd", probs.astype(vf.dtype), vf,
                   preferred_element_type=jnp.float32)
    return o.astype(qg.dtype)


def _paged_slots(tables, lens, n_valid, width: int, block_size: int,
                 n_blocks: int):
    """Where a group's queries sit: ``gpos`` i32[B, W] absolute positions
    lens[b]+j, and the pool coordinates (blk, off) their KV scatters to
    (positions j >= n_valid[b], or past the table, get the sentinel
    block ``n_blocks`` so the write drops)."""
    MB = tables.shape[1]
    j = jnp.arange(width)
    gpos = lens[:, None] + j[None, :]                     # [B, W]
    col = jnp.minimum(gpos // block_size, MB - 1)
    blk = jnp.take_along_axis(tables, col, axis=1)        # [B, W]
    blk = jnp.where((j[None, :] < n_valid[:, None])
                    & (gpos // block_size < MB), blk, n_blocks)
    return gpos, blk, gpos % block_size


def _attend_paged(cfg: ModelConfig, q, cache: dict, tables, lens, gpos,
                  block_size: int, backend: str, dtype):
    """Attention of one row group's roped queries q [B, S, H, Dh] over
    the (already written) pool ``cache``, query j of row b seeing kv
    positions [0, gpos[b, j]]. Returns o [B, S, H*Dh]."""
    B, S = q.shape[:2]
    n_blocks = cache["k"].shape[0]
    if backend == "flash":
        from repro.kernels.decode_attn import paged_attention
        # the Pallas interpreter is for hosts without a TPU only: on a
        # TPU the kernel always compiles with Mosaic
        o = paged_attention(
            q, cache["k"], cache["v"], tables, lens, block_size=block_size,
            interpret=jax.default_backend() != "tpu")
        return o.reshape(B, S, cfg.n_heads * cfg.d_head).astype(dtype)
    qg = q.reshape(B, S, cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head)
    if S == 1 and reads_pool(cfg, B, tables.shape[1], n_blocks,
                             block_size, backend):
        o = _attend_pool(cfg, qg[:, 0], cache, tables, lens, block_size)
        return o.reshape(B, S, cfg.n_heads * cfg.d_head)
    kg = _read_paged(cache, "k", tables, n_blocks)        # [B, MBbs, Kv, Dh]
    vg = _read_paged(cache, "v", tables, n_blocks)
    if S == 1:
        scope = _kv_seq_scope(kg.shape[1])
        if scope is not None:
            # sequence-sharded decode (ShardingPolicy.shard_kv_seq): the
            # gathered logical sequence shards over 'model' and each
            # device softmaxes only its local KV slice; the partials
            # merge with the LSE-combine collective — no device ever
            # materializes a row's full KV (the long-context layout).
            from repro.dist.collectives import lse_combine_decode_attention
            o = lse_combine_decode_attention(scope, qg[:, 0], kg, vg,
                                             lens + 1)[:, None]
        else:
            # single-token step: reference_attention keeps this
            # bit-identical to the contiguous-cache decode (and
            # GSPMD-shardable)
            o = reference_attention(qg, kg, vg, causal=False,
                                    kv_len=lens + 1)
    else:
        # per-(row, position) causal mask: kv position t visible to query
        # j of row b iff t <= lens[b]+j. S is small, so full scores are
        # [B, Kv, G, S, MB*bs] — same order as the reference decode path.
        scale = jnp.asarray(cfg.d_head ** -0.5, qg.dtype)
        s = _gqa_scores(qg * scale, kg)
        Skv = kg.shape[1]
        vis = jnp.arange(Skv)[None, None, :] <= gpos[:, :, None]  # [B,S,Skv]
        s = jnp.where(vis[:, None, None], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        o = jnp.moveaxis(_gqa_out(probs, vg), -2, 1).astype(dtype)
    return o.reshape(B, S, cfg.n_heads * cfg.d_head)


def _project_out(p, o):
    # bit-reproducible layout (exact_tp): gather the head-sharded o (a
    # concatenation — exact), multiply by the output-sharded wo with a
    # fully replicated contraction dim (no psum), gather the result;
    # identity when no exact_tp scope is active
    o = constrain_tp_exact(o)
    return constrain_tp_exact(o @ p["wo"])


class PagedGroup(NamedTuple):
    """One row group of a step: ``width`` tokens for each of its rows,
    row b at absolute positions lens[b]+j, n_valid[b] of them real,
    reading and writing through tables[b] (all-sentinel when idle)."""
    lens: jax.Array
    n_valid: jax.Array
    tables: jax.Array
    width: int


def _split_groups(t, groups):
    """Each group's [rows, width, ...] part of ``t``: ``t`` itself for
    one group, else a slice of the packed [1, N, ...] tokens."""
    if len(groups) == 1:
        return [t]
    out, i = [], 0
    for g in groups:
        rows = g.lens.shape[0]
        n = rows * g.width
        out.append(t[0, i:i + n].reshape((rows, g.width) + t.shape[2:]))
        i += n
    return out


def _join_groups(parts):
    """The groups' [rows, width, ...] parts in the step's token layout:
    one group's part as it is, several flattened row-major and
    concatenated in order ([N, ...])."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate([a.reshape((-1,) + a.shape[2:]) for a in parts])


def attn_step_grouped(p, cfg: ModelConfig, x, cos, sin, cache: dict,
                      groups, block_size: int, backend: str = "naive"):
    """ONE attention entry for every serving tick, through block tables.

    Row b of a group feeds ``width`` queries at absolute positions
    lens[b]+j; their KV scatters through the row's block table
    (positions j >= n_valid[b] are padding: sentinel writes drop) and
    each query attends causally to [0, lens[b]+j] — prior context plus
    the in-flight prefix before it. The same masking serves a decode row
    (one token), a verify row (1 + K drafts, K+1 scores per weight-stream
    read) and a prefill chunk attending to earlier chunks plus its own
    prefix.

    The projections run once on every group's tokens, all groups' KV
    lands in the pool in one store, and attention runs per group at the
    group's own width (_attend_paged: a width-1 group reads the pool
    whole or gathers its tables, a wider group attends causally;
    ``backend`` "flash" hands every width to the Pallas kernel).

    x: [rows, width, d] for one group, or [1, N, d] with several groups'
    tokens packed in order, each group's rows row-major (N = sum of rows
    x width); cos/sin laid out the same way. Returns (out shaped like x,
    new_cache)."""
    if cfg.mla:
        def packed(a):
            return a.reshape((1, -1) + a.shape[2:])
        out, new_cache = _mla_step(p, cfg, packed(x), packed(cos),
                                   packed(sin), cache, groups, block_size)
        return out.reshape(x.shape), new_cache
    n_blocks = cache["k"].shape[0]
    q, k, v = _qkv(p, cfg, x)
    q = rope.apply_rope(q, cos, sin)
    k = rope.apply_rope(k, cos, sin)
    slots = [_paged_slots(g.tables, g.lens, g.n_valid, g.width,
                          block_size, n_blocks) for g in groups]
    blk = _join_groups([b for _, b, _ in slots])
    off = _join_groups([o for _, _, o in slots])
    new_cache = {
        **_store_paged(cache, "k", blk, off,
                       k.reshape(blk.shape + k.shape[2:])),
        **_store_paged(cache, "v", blk, off,
                       v.reshape(blk.shape + v.shape[2:]))}
    outs = [_attend_paged(cfg, qg, new_cache, g.tables, g.lens, gpos,
                          block_size, backend, x.dtype)
            for g, qg, (gpos, _, _) in zip(groups, _split_groups(q, groups),
                                          slots)]
    o = _join_groups(outs)
    return _project_out(p, o.reshape(x.shape[:2] + o.shape[-1:])), \
        new_cache


# ---------------------------------------------------------------------------
# Latent attention (MLA): per token the pool holds [c | k_pe] — the
# RMS-normed latent c = norm(x Wkv_a[:, :r]) and the roped key k_pe its
# heads share. Attention runs in latent space: Wkv_b's key half folds into
# each head's query (q_nope -> r wide), its value half into the output,
# so one gathered column of r + rope values serves every head (MQA with
# one KV head; the first r values are the value).


def latent_width(cfg: ModelConfig) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def init_mla(key, cfg: ModelConfig, dtype):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rd, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {"wq": layers.dense_init(ks[0], (d, H * (nope + rd)), dtype),
            "wkv_a": layers.dense_init(ks[1], (d, r + rd), dtype),
            "kv_norm": jnp.ones((r,), dtype),
            "wkv_b": layers.dense_init(ks[2], (r, H * (nope + dv)), dtype),
            "wo": layers.dense_init(ks[3], (H * dv, d), dtype)}


def _wkv_b(p, cfg: ModelConfig):
    """Wkv_b as [r, H, qk_nope + v]: per head its key half, then its
    value half (the published kv_b_proj layout)."""
    return p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)


def _mla_qkv(p, cfg: ModelConfig, x, cos, sin):
    """x [B, S, d] -> (q [B, S, H, r + rope]: the absorbed query
    q_nope Wkv_b^k beside the roped q_pe; kv [B, S, 1, r + rope]: the
    pool entry [norm(c) | rope(k_pe)]). cos/sin cover the rope width."""
    B, S, _ = x.shape
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, -1)
    ckv = x @ p["wkv_a"]
    c = layers.rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope.apply_rope(ckv[..., None, r:], cos, sin)
    q_pe = rope.apply_rope(q[..., nope:], cos, sin)
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :nope],
                       _wkv_b(p, cfg)[..., :nope])
    return (jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], -1),
            jnp.concatenate([c[:, :, None], k_pe], -1))


def _mla_attend(cfg: ModelConfig, q, kv, gpos):
    """Latent attention of queries q [B, S, H, r + rope] over latent rows
    kv [B, T, 1, r + rope], query j of row b seeing rows t <= gpos[b, j];
    softmax scale 1/sqrt(qk_nope + qk_rope) as published. Returns the
    latent output f32[B, S, H, r]."""
    s = _gqa_scores(q[:, :, None], kv) * (cfg.d_head ** -0.5)
    vis = jnp.arange(kv.shape[1])[None, None, :] <= gpos[:, :, None]
    s = jnp.where(vis[:, None, None], s, NEG_INF)     # [B, 1, H, S, T]
    o = _gqa_out(jax.nn.softmax(s, axis=-1), kv[..., :cfg.kv_lora_rank])
    return jnp.moveaxis(o[:, 0], 1, 2)


def _mla_out(p, cfg: ModelConfig, o, dtype):
    """Latent output [B, S, H, r] -> values through Wkv_b's value half,
    then Wo: [B, S, d]."""
    B, S = o.shape[:2]
    v = jnp.einsum("bshr,rhv->bshv", o.astype(dtype),
                   _wkv_b(p, cfg)[..., cfg.qk_nope_head_dim:])
    return _project_out(p, v.reshape(B, S, -1))


def _mla_step(p, cfg: ModelConfig, x, cos, sin, cache: dict, groups,
              block_size: int):
    """attn_step_grouped for latent attention: projections on the packed
    tokens x [1, N, d], every group's latents into the pool leaf ``kv``
    in one store, then per group one gather of latent columns for all
    heads. Returns (out [1, N, d], new_cache)."""
    q, kv = _mla_qkv(p, cfg, x, cos, sin)
    n_blocks = cache["kv"].shape[0]
    slots = [_paged_slots(g.tables, g.lens, g.n_valid, g.width,
                          block_size, n_blocks) for g in groups]
    blk = jnp.concatenate([b.reshape(-1) for _, b, _ in slots])
    off = jnp.concatenate([o.reshape(-1) for _, _, o in slots])
    new_cache = _store_paged(cache, "kv", blk, off, kv[0])
    outs, i = [], 0
    for g, (gpos, _, _) in zip(groups, slots):
        rows = g.lens.shape[0]
        n = rows * g.width
        qg = q[0, i:i + n].reshape(rows, g.width, *q.shape[2:])
        ctx = _read_paged(new_cache, "kv", g.tables, n_blocks)
        outs.append(_mla_attend(cfg, qg, ctx, gpos).reshape(
            n, *q.shape[2:3], cfg.kv_lora_rank))
        i += n
    return _mla_out(p, cfg, jnp.concatenate(outs)[None], x.dtype), \
        new_cache
