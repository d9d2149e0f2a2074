"""ModelRunner: ONE batched ``step(StepBatch) -> StepOutput`` for serving.

The engine used to juggle three separately-jitted model entries (prefill
chunk, paged decode, spec verify) plus a greedy-only sampling helper —
every new scenario multiplied code paths. The runner collapses them onto
the paper's one-matvec-datapath shape: a single model entry
(models.transformer.forward_step) serves chunked-prefill rows, decode
rows, and K+1 verify rows in the same step, so decode never stalls
behind prefill ticks (continuous batching) and new phases are a new row
kind, not a new model entry.

Shape discipline: one step shape, compiled once per (W, P). Every tick
is a generation group of ``max_batch`` rows, one per slot, at width W
(1 for decode, k_max+1 when a verify or resync row rides), plus a
prefill group of P rows of the chunk width C, P the number of prefill
rows rounded up to a power of four capped at ``max_batch`` (P = 0: no
prefill group is traced). Idle slots carry sentinel tables. The
executables are named for their shape: ``serve_step_w<W>`` and
``serve_step_w<W>_p<P>x<C>``; decode rows are never padded to the
chunk, verify rows never to more than k_max+1, and the first tick with
a prefill group builds every P of its W at once. The runner owns the
device cache; the engine republishes the host-truth ``lens`` and block
tables before every step (the device never advances them — only the
engine knows what actually committed, e.g. after speculative
acceptance).

The attention read path is pluggable (``ServeConfig.attn_backend``):
"naive" gathers blocks into a logical sequence (reference, shardable),
except that a width-1 group whose tables span more blocks than the pool
holds attends over the whole pool once (``kv_read``); "flash" hands the
block pools + tables to the Pallas paged-attention kernel
(kernels.decode_attn.paged_attention), which covers every group width
— single-token decode, K+1 verify, and prefill chunks — with per-row
causal masking resolved in-kernel.

Sharded serving (``mesh`` + ``policy``): the runner is the mesh-aware
boundary. Weights shard over the 'model' axis (dist.sharding.
params_shardings), the paged block pool shards its KV-HEAD axis
(cache_shardings with paged=True), and ``step`` stays ONE jitted entry
whose inputs are committed sharded arrays and whose out_shardings pin
the cache layout stable across ticks. Everything above (engine,
scheduler, paged_kv, prefix cache) sees exactly the same host-side
world as on one device — block ids, refcounts, COW pairs and tables are
global, only the device bytes behind them are partitioned. With
``policy.shard_kv_seq`` single-token decode attention additionally
shards the gathered KV sequence and merges partial softmaxes via the
LSE-combine collective (dist.collectives).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.dist import sharding as shd
from repro.models import attention
from repro.obs.trace import NULL_TRACER

# row phases (StepBatch.phase values)
IDLE, PREFILL, DECODE, VERIFY = 0, 1, 2, 3

BACKENDS = ("naive", "flash")

# Module-level jit cache for single-device runners, keyed by the facts
# the trace depends on (ModelConfig is frozen/hashable). Every Engine
# builds its own ModelRunner; without this, each instance would re-trace
# identical steps — the async differential fuzz harness builds hundreds
# of engine pairs per run, which must share compilations. Mesh runners
# keep per-instance jits (out_shardings close over live mesh state).
_JIT_CACHE: Dict[tuple, callable] = {}

# device-side stop-sequence bounds for the async decode burst: stops up
# to STOP_L tokens, STOP_NS per request, get on-device early exit;
# longer/extra stops still match host-side at reconcile (identity is
# unaffected — the device match only trims overrun compute)
STOP_L = 4
STOP_NS = 2


def prefill_buckets(max_batch: int) -> List[int]:
    """Row counts a step's prefill group compiles for: powers of
    four below ``max_batch``, then ``max_batch``. Each bucket is one more
    executable to lower at set-up (most of a second at Qwen3-4B on a
    v5e host), against about 1.3 ms of device time per padded prefill
    row in a tick: powers of two would double the set-up for little."""
    out, p = [], 1
    while p < max_batch:
        out.append(p)
        p *= 4
    return out + [max_batch]


def prefill_bucket_for(n_prefill: int, max_batch: int) -> int:
    """Smallest prefill-group bucket holding ``n_prefill`` rows (0 for
    none)."""
    if not n_prefill:
        return 0
    return next(p for p in prefill_buckets(max_batch) if p >= n_prefill)


def _last_valid(logits, n_valid):
    """Each row's logits at its last valid position: [rows, V]."""
    idx = jnp.clip(n_valid - 1, 0, logits.shape[1] - 1)
    idx = idx.reshape((-1,) + (1,) * (logits.ndim - 1))
    return jnp.take_along_axis(logits, idx, axis=1)[:, 0]


def step_fn(model, block_size: int, backend: str):
    """The step function of every tick (Model.forward_step): (params,
    tokens, pf_tokens, cache, n_valid, pf_slot, pf_start, pf_valid) ->
    (generation logits [B, W, V], prefill logits [P, C, V] or None,
    last-valid logits by slot, cache, routing counts — None without MoE
    layers). A prefill row's last logits land in its slot's row; a
    padding row's slot id is out of range and its row drops.

    Beside a prefill group a width-1 generation group's one position is
    sliced out; the decode-only step keeps the gather at index 0, so its
    executable stays the program the benchmark measured (a slice there
    would drop the gather fusions over the [B, 1, V] logits, a gain not
    yet measured on the chip)."""
    def run(params, tokens, pf_tokens, cache, n_valid, pf_slot, pf_start,
            pf_valid):
        gen, pf, cache, counts = model.forward_step(
            params, tokens, pf_tokens, cache, n_valid, pf_slot, pf_start,
            pf_valid, block_size, backend=backend)
        if pf is None:
            return gen, None, _last_valid(gen, n_valid), cache, counts
        last = gen[:, 0] if gen.shape[1] == 1 else _last_valid(gen, n_valid)
        last = last.at[pf_slot].set(_last_valid(pf, pf_valid), mode="drop")
        return gen, pf, last, cache, counts
    return run


def _batch_inputs(batch: "StepBatch", pf_slots: np.ndarray, C: int,
                  tokens=None):
    """A batch's per-slot rows as the step's device inputs after params:
    (tokens, pf_tokens, n_valid, pf_slot, pf_start, pf_valid). The
    generation group is every slot at width W, its prefill slots idle
    (no valid token); the prefill rows ``pf_slots`` are packed first into
    the prefill group and the rest of it padded with slot id B (all four
    prefill inputs None when P = 0). ``tokens`` overrides the generation
    tokens with a device array."""
    B, W, P = batch.tokens.shape[0], batch.width, batch.prefill_bucket
    if tokens is None:
        tokens = jnp.asarray(batch.tokens[:, :W])
    n_valid = jnp.asarray(np.where(batch.phase != PREFILL, batch.n_valid, 0))
    if not P:
        return tokens, None, n_valid, None, None, None
    n = len(pf_slots)
    pf_tokens = np.zeros((P, C) + batch.tokens.shape[2:], np.int32)
    pf_tokens[:n, :batch.tokens.shape[1]] = batch.tokens[pf_slots, :C]
    slot = np.full((P,), B, np.int32)
    slot[:n] = pf_slots
    start = np.zeros((P,), np.int32)
    start[:n] = batch.row_start[pf_slots]
    valid = np.zeros((P,), np.int32)
    valid[:n] = batch.n_valid[pf_slots]
    return (tokens, jnp.asarray(pf_tokens), n_valid, jnp.asarray(slot),
            jnp.asarray(start), jnp.asarray(valid))


@dataclasses.dataclass
class StepBatch:
    """Host-side description of one step: flat tokens plus a per-row
    (phase, start, valid-length) descriptor and the block tables.

    tokens: i32[B, S] (or [B, S, nc] for codebook models) — row b's valid
    tokens occupy [0, n_valid[b]); the rest is padding whose KV writes
    drop at the sentinel. row_start[b] is the absolute position of the
    row's first token (its committed context length; the prefill frontier
    for PREFILL rows). phase[b] routes the row: DECODE/VERIFY rows run in
    the generation group at ``width`` W (the sparse decode FFN), PREFILL
    rows in the prefill group of ``prefill_bucket`` P rows of the chunk
    width (the dense FFN); IDLE rows are fully masked (sentinel tables,
    garbage logits). S is W, or the chunk width when prefill rows may
    ride along. ``add_row`` raises P to cover the prefill rows added."""

    tokens: np.ndarray
    row_start: np.ndarray
    n_valid: np.ndarray
    phase: np.ndarray
    tables: np.ndarray
    width: int = 1
    prefill_bucket: int = 0

    def add_row(self, slot: int, phase: int, tokens, start: int) -> None:
        toks = np.asarray(tokens, np.int32)
        self.tokens[slot, :len(toks)] = toks
        self.row_start[slot] = start
        self.n_valid[slot] = len(toks)
        self.phase[slot] = phase
        if phase == PREFILL:
            n = int(np.sum(self.phase == PREFILL))
            self.prefill_bucket = max(
                self.prefill_bucket,
                prefill_bucket_for(n, self.tokens.shape[0]))


@dataclasses.dataclass
class StepOutput:
    """Device results of one step. ``logits[b, j]`` is the generation
    group's distribution for the token FOLLOWING tokens[b, j] ([B, W,
    V(, nc x V for codebooks)]); ``prefill_logits`` the prefill group's
    [P, C, V] and ``prefill_slots`` the slot of each of its rows (None
    without a prefill group). ``last_logits[b]`` is slot b's logits at
    its last valid position (what decode rows and prompt-completing
    prefill rows sample from). ``row_logits`` pulls one slot's row to
    host lazily, from whichever group ran it — verify rows need the full
    chain, everyone else only samples from ``last_logits``."""

    logits: jax.Array
    last_logits: jax.Array     # [B, V] / [B, nc, V]
    prefill_logits: Optional[jax.Array] = None
    prefill_slots: Optional[np.ndarray] = None
    # MoE models: the step's routing counts, i32[3] on the device
    # (transformer._paged_stack's ``counts``)
    moe_counts: Optional[jax.Array] = None
    _np: Optional[np.ndarray] = None
    _pf_np: Optional[np.ndarray] = None

    def row_logits(self, slot: int) -> np.ndarray:
        if self.prefill_slots is not None:
            hit = np.flatnonzero(self.prefill_slots == slot)
            if hit.size:
                if self._pf_np is None:
                    self._pf_np = np.asarray(self.prefill_logits)
                return self._pf_np[hit[0]]
        if self._np is None:
            self._np = np.asarray(self.logits)
        return self._np[slot]


class ModelRunner:
    """Owns the device-side paged cache and the bucketed jit instances of
    ``Model.forward_step``; the engine (a pure host-side scheduler) builds
    a StepBatch per tick and calls ``step``."""

    def __init__(self, model, params, scfg: ServeConfig,
                 dtype=jnp.float32, mesh=None, policy=None, tracer=None):
        """``mesh``/``policy`` (a jax Mesh + dist.sharding.ShardingPolicy)
        turn on sharded serving: params and the paged pool are device_put
        to their mesh shardings here, and every compiled step pins them
        via out_shardings. Single-device serving passes neither and pays
        nothing. ``tracer`` (repro.obs) wraps each step in
        device_dispatch/device_wait spans; the fence that makes device
        time attributable only runs when tracing is enabled — the
        untraced path keeps async dispatch untouched."""
        cfg: ModelConfig = model.cfg
        if scfg.attn_backend not in BACKENDS:
            raise ValueError(f"unknown attn_backend "
                             f"{scfg.attn_backend!r}; known: {BACKENDS}")
        if scfg.attn_backend == "flash" and scfg.kv_quant:
            raise ValueError(
                "attn_backend='flash' reads fp block pools; int8 KV "
                "(kv_quant) needs the naive dequantizing gather")
        if cfg.mla and scfg.attn_backend == "flash":
            raise ValueError(
                "attn_backend='flash' reads k/v block pools; latent "
                "attention's pool holds one latent leaf (naive only)")
        if mesh is not None and scfg.attn_backend != "naive":
            raise ValueError(
                "sharded serving (ServeConfig.mesh) needs the GSPMD-"
                "shardable attn_backend='naive' read path; the Pallas "
                "flash kernel addresses one device's pool")
        self.model = model
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.mesh = mesh
        self.policy = policy if policy is not None else shd.ShardingPolicy()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.cache = model.init_paged_cache(
            scfg.max_batch, scfg.pool_blocks, scfg.block_size,
            scfg.blocks_per_seq, dtype, int8_kv=scfg.kv_quant)
        self._cache_shardings = None
        self._repl = None
        if mesh is not None:
            self.params = jax.device_put(
                params, shd.params_shardings(params, cfg, mesh,
                                             self.policy))
            csh = shd.cache_shardings(cfg, mesh, scfg.max_batch,
                                      policy=self.policy, paged=True)
            self._cache_shardings = jax.tree_util.tree_map_with_path(
                csh, self.cache)
            self.cache = jax.tree.map(jax.device_put, self.cache,
                                      self._cache_shardings)
            self._repl = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
        # how every width-1 generation group (decode ticks with or
        # without a prefill group, bursts) reads the pool: "pool" attends
        # over the whole leaf, "gather" gathers every table column,
        # "kernel" is the flash kernel's table walk. Fixed by the static
        # shapes (attention.reads_pool), under the mesh scope the steps
        # are traced in.
        with shd.activation_sharding_scope(mesh, self.policy) \
                if mesh is not None else contextlib.nullcontext():
            pool = attention.reads_pool(
                cfg, scfg.max_batch, scfg.blocks_per_seq, scfg.pool_blocks,
                scfg.block_size, scfg.attn_backend)
        self.kv_read = "kernel" if scfg.attn_backend == "flash" \
            else "pool" if pool else "gather"
        # generation-group widths: decode, and verify / resync rows
        self.widths = [1] + ([scfg.spec.k_max + 1]
                             if scfg.spec is not None else [])
        self.prefill_buckets = prefill_buckets(scfg.max_batch)
        self._fns: Dict[tuple, callable] = {}
        self._built = set()         # widths whose prefill buckets exist
        self.n_steps = 0            # device steps dispatched (async
        #                             engines add burst iterations on top)

    # --- batch construction ------------------------------------------------
    def prefill_bucket_for(self, n_prefill: int) -> int:
        """Smallest prefill-group bucket holding ``n_prefill`` rows."""
        return prefill_bucket_for(n_prefill, self.scfg.max_batch)

    def new_batch(self, max_valid: int, tables: np.ndarray,
                  n_prefill: int = 0) -> StepBatch:
        """An empty batch whose generation group is the narrowest width
        holding rows of ``max_valid`` tokens (the widest when none does:
        only a prefill row is that long), with a prefill group of
        ``n_prefill`` rows. The token buffer widens to the chunk when
        prefill rows may ride along."""
        B, C = self.scfg.max_batch, self.scfg.prefill_chunk
        W = next((w for w in self.widths if w >= max_valid),
                 self.widths[-1])
        S = max(W, C) if n_prefill or max_valid > W else W
        nc = (self.cfg.n_codebooks,) if self.cfg.n_codebooks else ()
        return StepBatch(tokens=np.zeros((B, S) + nc, np.int32),
                         row_start=np.zeros((B,), np.int32),
                         n_valid=np.zeros((B,), np.int32),
                         phase=np.full((B,), IDLE, np.int32),
                         tables=np.asarray(tables, np.int32), width=W,
                         prefill_bucket=self.prefill_bucket_for(n_prefill))

    # --- the one step ------------------------------------------------------
    def _fn(self, width: int, prefill_bucket: int):
        """One jit per step shape (W, P), named for it:
        ``serve_step_w1`` (decode), ``serve_step_w5`` (verify rows of
        K+1 = 5), ``serve_step_w1_p4x32`` (decode rows beside four
        prefill rows of 32). A compile event and the profiler's dispatch
        span say which shape ran. P = 0 — the serving steady state —
        compiles to the pure sparse decode FFN and never streams the
        dense W_down."""
        key = (width, prefill_bucket)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        mdl, bs = self.model, self.scfg.block_size
        backend = self.scfg.attn_backend
        mesh, policy = self.mesh, self.policy
        name = f"serve_step_w{width}"
        if prefill_bucket:
            name += f"_p{prefill_bucket}x{self.scfg.prefill_chunk}"
        run = step_fn(mdl, bs, backend)
        if mesh is None:
            # shared across runner instances
            gkey = (mdl.cfg, bs, backend, name)
            fn = _JIT_CACHE.get(gkey)
            if fn is None:
                run.__name__ = run.__qualname__ = name
                fn = _JIT_CACHE[gkey] = jax.jit(run)
        else:
            # trace under the activation-sharding scope (model code
            # consults it for the seq-sharded LSE decode path) and pin
            # the cache's layout so it never drifts across ticks; logits
            # come back replicated — sampling and the verify chain read
            # them host-side, as do the routing counts.
            base = run

            def run(*args):
                with shd.activation_sharding_scope(mesh, policy):
                    return base(*args)

            run.__name__ = run.__qualname__ = name
            fn = jax.jit(run, out_shardings=(
                self._repl, self._repl, self._repl, self._cache_shardings,
                self._repl))
        self._fns[key] = fn
        return fn

    def step_args(self, width: int, prefill_bucket: int, spec):
        """The step's arguments after params, in the shape of (W, P):
        ``spec(shape, dtype)`` makes each array (zeros to run it,
        ShapeDtypeStruct to lower it); the cache is the caller's."""
        B, C = self.scfg.max_batch, self.scfg.prefill_chunk
        P = prefill_bucket
        nc = (self.cfg.n_codebooks,) if self.cfg.n_codebooks else ()
        pf_tokens = spec((P, C) + nc, jnp.int32) if P else None
        rows = [spec((P,), jnp.int32) if P else None for _ in range(3)]
        return (spec((B, width) + nc, jnp.int32), pf_tokens,
                spec((B,), jnp.int32), *rows)

    def _build_prefill_buckets(self, width: int) -> None:
        """Build the executable of every prefill bucket at generation
        width ``width``, each run once on an all-idle batch (every write
        drops at the sentinel, the pool is left as it was): the first tick
        with a prefill row pays for all of them, and a later tick with
        more prefill rows compiles nothing."""
        B = self.scfg.max_batch
        n_blocks = jax.tree.leaves(self.cache["units"])[0].shape[1]
        cache = dict(self.cache, block_tables=jnp.full_like(
            self.cache["block_tables"], n_blocks))
        for P in self.prefill_buckets:
            toks, pf, n_valid, slot, start, valid = self.step_args(
                width, P, jnp.zeros)
            out = self._fn(width, P)(
                self.params, toks, pf, cache, n_valid,
                jnp.full_like(slot, B), start, valid)
            jax.block_until_ready(out)
            del out
        self._built.add(width)

    def step(self, batch: StepBatch, fence: bool = True,
             tokens=None) -> StepOutput:
        """Run one step: republish host-truth lens/tables, execute the
        (W, P) jit, return per-position and last-valid logits.

        ``fence=False`` is the async engine's double-buffered dispatch
        (docs/async.md): even under tracing with ``fence_device`` on, the
        call returns as soon as the step is dispatched — the engine
        reconciles the results one tick later, attributing the deferred
        wait to its sample_sync span instead. ``tokens`` overrides the
        generation group's tokens with a DEVICE array ([B, W]), letting
        tick t+1's input chain on tick t's still-in-flight sampled tokens
        without a host round-trip."""
        W, P = batch.width, batch.prefill_bucket
        pf_slots = np.flatnonzero(batch.phase == PREFILL)
        if P and W not in self._built:
            self._build_prefill_buckets(W)
        tr = self.tracer
        attrs = {"prefill_bucket": P} if P else {}
        with tr.span("device_dispatch", width=batch.tokens.shape[1],
                     has_prefill=bool(pf_slots.size), **attrs):
            self.cache["lens"] = jnp.asarray(batch.row_start)
            self.cache["block_tables"] = jnp.asarray(batch.tables)
            toks, pf, *rows = _batch_inputs(batch, pf_slots,
                                            self.scfg.prefill_chunk, tokens)
            logits, pf_logits, last, self.cache, counts = self._fn(W, P)(
                self.params, toks, pf, self.cache, *rows)
        self.n_steps += 1
        if fence and tr.enabled and tr.cfg.fence_device:
            # fence so device_wait covers actual execution, not just
            # dispatch — host/device attribution depends on this; the
            # untraced path never blocks (async dispatch preserved)
            with tr.span("device_wait"):
                jax.block_until_ready((logits, last))
        return StepOutput(logits=logits, last_logits=last,
                          prefill_logits=pf_logits,
                          prefill_slots=pf_slots if P else None,
                          moe_counts=counts)

    # --- device-resident decode burst (async engine, docs/async.md) ---
    def decode_burst(self, sampled: bool, k_max: int):
        """One jit per (sampled, k_max): up to k_max single-token decode
        ticks chained inside a device ``lax.while_loop`` with per-row
        early exit (budget / on-device stop match). The input cache is
        DONATED — callers must rebind ``runner.cache`` to the returned
        cache. Greedy bursts compile without the filter/categorical
        machinery, mirroring the synchronous greedy fast path."""
        assert self.mesh is None, \
            "decode_burst is single-device (the async engine gates loop " \
            "mode off under ServeConfig.mesh)"
        mdl, bs = self.model, self.scfg.block_size
        backend = self.scfg.attn_backend
        key = (mdl.cfg, bs, backend, "burst", sampled, k_max)
        fn = _JIT_CACHE.get(key)
        if fn is None:
            from repro.serve import sampling as smp

            def run(params, cache, tables, tok0, lens0, alive0, budget,
                    stops, stop_len, hist0, keys, temp, top_k, top_p,
                    k_ticks):
                B = tok0.shape[0]
                if sampled:
                    no_presence = jnp.zeros((B, 1), bool)
                    no_rep = jnp.ones((B,), jnp.float32)

                    def sample_fn(last, i):
                        # rep penalty rows never reach the burst (they
                        # need the host token stream), so presence is a
                        # broadcastable dummy
                        return smp._sample_batch(last, no_presence, temp,
                                                 top_k, top_p, no_rep,
                                                 keys[i])
                else:
                    def sample_fn(last, i):
                        return smp._greedy_batch(last)

                return mdl.decode_burst(params, cache, tables, tok0,
                                        lens0, alive0, budget, stops,
                                        stop_len, hist0, sample_fn, bs,
                                        backend, k_ticks, k_max)

            run.__name__ = run.__qualname__ = \
                f"serve_burst_k{k_max}" + ("_sampled" if sampled else "")
            fn = jax.jit(run, donate_argnums=(1,))
            _JIT_CACHE[key] = fn
        return fn

    # --- block maintenance --------------------------------------------------
    def apply_perm(self, perm: np.ndarray) -> None:
        """Apply a pool defrag permutation to the device block pools
        (new storage row i = old row perm[i]). Block ids are GLOBAL under
        sharding — the gather runs along the unsharded block axis, so
        every shard permutes its local head slice identically."""
        p = jnp.asarray(perm)
        self._map_pools(lambda a: jnp.take(a, p, axis=1))

    def copy_blocks(self, pairs) -> None:
        """Copy-on-write: duplicate pool storage rows src -> dst across
        every layer's block pools (all leaves, int8 scales included).
        The host side (paged_kv.cow_for_write) already rewrote the block
        table; this mirrors the bytes so the writer's private copy starts
        bit-identical to the shared original. Like apply_perm, this is a
        block-axis op: under sharding each device copies its own head
        slice of the block — no cross-device traffic."""
        if not pairs:
            return
        src = jnp.asarray([p[0] for p in pairs], jnp.int32)
        dst = jnp.asarray([p[1] for p in pairs], jnp.int32)
        self._map_pools(lambda a: a.at[:, dst].set(a[:, src]))

    def import_blocks_from(self, src: "ModelRunner", src_ids,
                           dst_ids) -> None:
        """Disagg KV handoff (serve.disagg): copy block storage rows
        ``src_ids`` of ``src``'s pool into rows ``dst_ids`` of THIS
        runner's pool, across every layer's pools (all leaves — int8
        scales included, so quantized KV survives bit-identical). Same
        block-axis primitive family as copy_blocks; under a shared mesh
        the per-leaf device_put reshards src bytes into this pool's
        layout before the scatter."""
        if not len(src_ids):
            return
        s = jnp.asarray(np.asarray(src_ids, np.int32))
        d = jnp.asarray(np.asarray(dst_ids, np.int32))
        self._map_pools(lambda a, b: a.at[:, d].set(b[:, s].astype(a.dtype)),
                        src.cache)

    def _map_pools(self, fn, *others) -> None:
        """Apply ``fn`` to every block-pool leaf (block axis 1 of each
        stack: "units", and "lead" where the model has leading dense
        layers), with the same leaves of ``others`` as extra arguments;
        then re-commit the leaves to their mesh shardings (a no-op when
        GSPMD already kept the layout, and on single-device runners)."""
        for key in ("units", "lead"):
            if key not in self.cache:
                continue
            self.cache[key] = jax.tree.map(
                fn, self.cache[key], *(o[key] for o in others))
            if self._cache_shardings is not None:
                self.cache[key] = jax.tree.map(
                    jax.device_put, self.cache[key],
                    self._cache_shardings[key])


__all__ = ["BACKENDS", "DECODE", "IDLE", "ModelRunner", "PREFILL",
           "STOP_L", "STOP_NS", "StepBatch", "StepOutput", "VERIFY",
           "prefill_bucket_for", "prefill_buckets", "step_fn"]
