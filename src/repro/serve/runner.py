"""ModelRunner: ONE batched ``step(StepBatch) -> StepOutput`` for serving.

The engine used to juggle three separately-jitted model entries (prefill
chunk, paged decode, spec verify) plus a greedy-only sampling helper —
every new scenario multiplied code paths. The runner collapses them onto
the paper's one-matvec-datapath shape: a single jitted function
(models.transformer.forward_step) serves chunked-prefill rows, decode
rows, and K+1 verify rows in the same fixed-shape batch, so decode never
stalls behind prefill ticks (continuous batching) and new phases are a
new row kind, not a new model entry.

Shape discipline: three kinds of tick, each compiled once per shape.
A decode tick runs every row at width 1 (``serve_step_w1``). A tick
that carries prefill chunks and no verify row is grouped
(``serve_step_w1_prefill_p<P>x<C>``): a width-1 decode group of
``max_batch`` rows, one per slot, and a prefill group of P rows of the
chunk width C, P the number of prefill rows rounded up to a power of four
capped at ``max_batch`` — so decode rows are never padded to the chunk,
and the first grouped tick builds every P at once. Ticks with verify
rows run every row at the widest row's bucket (k_max+1, or the chunk
width when prefill rows ride along: ``serve_step_w<S>[_prefill]``). The
runner owns the device cache; the engine republishes the host-truth
``lens`` and block tables before every step (the device never advances
them — only the engine knows what actually committed, e.g. after
speculative acceptance).

The attention read path is pluggable (``ServeConfig.attn_backend``):
"naive" gathers blocks into a logical sequence (reference, shardable),
except that a width-1 group whose tables span more blocks than the pool
holds attends over the whole pool once (``kv_read``); "flash" hands the
block pools + tables to the Pallas paged-attention kernel
(kernels.decode_attn.paged_attention), which covers every row width of
the unified step — single-token decode, K+1 verify, and prefill chunks
— with per-row causal masking resolved in-kernel.

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
    """Row counts a grouped step's prefill group compiles for: powers of
    four below ``max_batch``, then ``max_batch``. Each bucket is one more
    executable to lower at set-up (most of a second at Qwen3-4B on a
    v5e host), against about 1.3 ms of device time per padded prefill
    row in a tick: powers of two would double the set-up for little."""
    out, p = [], 1
    while p < max_batch:
        out.append(p)
        p *= 4
    return out + [max_batch]


def uniform_step(model, block_size: int, backend: str, has_prefill: bool):
    """The step function of a uniform tick (every row at one width):
    (params, tokens, cache, n_valid, is_prefill) -> (logits, last-valid
    logits per row, cache, routing counts — None without MoE layers)."""
    def run(params, tokens, cache, n_valid, is_prefill):
        logits, cache, counts = model.forward_step(
            params, tokens, cache, n_valid, is_prefill, block_size,
            backend=backend, has_prefill=has_prefill, with_counts=True)
        idx = jnp.clip(n_valid - 1, 0, logits.shape[1] - 1)
        idx = idx.reshape((-1,) + (1,) * (logits.ndim - 1))
        last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
        return logits, last, cache, counts
    return run


def grouped_step(model, block_size: int, backend: str):
    """The step function of a grouped tick (Model.forward_step_grouped):
    (params, dec_tokens, pf_tokens, cache, dec_valid, pf_slot, pf_start,
    pf_valid) -> (decode logits, prefill logits, last-valid logits by
    slot, cache, routing counts). A prefill row's last logits land in its
    slot's row; a padding row's slot id is out of range and its row
    drops."""
    def run(params, dec_tokens, pf_tokens, cache, dec_valid, pf_slot,
            pf_start, pf_valid):
        dec, pf, cache, counts = model.forward_step_grouped(
            params, dec_tokens, pf_tokens, cache, dec_valid, pf_slot,
            pf_start, pf_valid, block_size, backend=backend,
            with_counts=True)
        idx = jnp.clip(pf_valid - 1, 0, pf.shape[1] - 1)
        idx = idx.reshape((-1,) + (1,) * (pf.ndim - 1))
        pf_last = jnp.take_along_axis(pf, idx, axis=1)[:, 0]
        last = dec[:, 0].at[pf_slot].set(pf_last, mode="drop")
        return dec, pf, last, cache, counts
    return run


def _grouped_args(batch: "StepBatch", pf_slots: np.ndarray):
    """A grouped batch's per-slot rows as the two groups' device inputs:
    the decode group by slot, the prefill rows ``pf_slots`` packed first
    into the prefill group and the rest of it padded with slot id B."""
    B = batch.tokens.shape[0]
    P = batch.prefill_bucket
    n = len(pf_slots)
    dec = batch.phase == DECODE
    dec_tokens = np.where(
        dec.reshape((B,) + (1,) * (batch.tokens.ndim - 1)),
        batch.tokens[:, :1], 0)
    pf_tokens = np.zeros((P,) + batch.tokens.shape[1:], np.int32)
    pf_tokens[:n] = batch.tokens[pf_slots]
    slot = np.full((P,), B, np.int32)
    slot[:n] = pf_slots
    start = np.zeros((P,), np.int32)
    start[:n] = batch.row_start[pf_slots]
    valid = np.zeros((P,), np.int32)
    valid[:n] = batch.n_valid[pf_slots]
    return tuple(jnp.asarray(a) for a in (
        dec_tokens, pf_tokens, np.where(dec, batch.n_valid, 0), slot,
        start, valid))


@dataclasses.dataclass
class StepBatch:
    """Host-side description of one unified step: flat tokens plus a
    per-row (phase, start, valid-length) descriptor and the block tables.

    tokens: i32[B, S] (or [B, S, nc] for codebook models) — row b's valid
    tokens occupy [0, n_valid[b]); the rest is padding whose KV writes
    drop at the sentinel. row_start[b] is the absolute position of the
    row's first token (its committed context length; the prefill frontier
    for PREFILL rows). phase[b] routes per-row math: PREFILL rows use the
    dense FFN, DECODE/VERIFY the sparse decode path; IDLE rows are fully
    masked (sentinel tables, garbage logits).

    ``prefill_bucket`` P > 0 makes the step grouped (S is then the chunk
    width): the runner runs the DECODE rows as a width-1 group and the
    PREFILL rows as a [P, S] group, and never pads a decode row to S."""

    tokens: np.ndarray
    row_start: np.ndarray
    n_valid: np.ndarray
    phase: np.ndarray
    tables: np.ndarray
    prefill_bucket: int = 0

    @classmethod
    def empty(cls, max_batch: int, width: int, tables: np.ndarray,
              n_codebooks: int = 0, prefill_bucket: int = 0
              ) -> "StepBatch":
        shape = (max_batch, width, n_codebooks) if n_codebooks \
            else (max_batch, width)
        return cls(tokens=np.zeros(shape, np.int32),
                   row_start=np.zeros((max_batch,), np.int32),
                   n_valid=np.zeros((max_batch,), np.int32),
                   phase=np.full((max_batch,), IDLE, np.int32),
                   tables=np.asarray(tables, np.int32),
                   prefill_bucket=prefill_bucket)

    def add_row(self, slot: int, phase: int, tokens, start: int) -> None:
        toks = np.asarray(tokens, np.int32)
        self.tokens[slot, :len(toks)] = toks
        self.row_start[slot] = start
        self.n_valid[slot] = len(toks)
        self.phase[slot] = phase


@dataclasses.dataclass
class StepOutput:
    """Device results of one step. ``logits[b, j]`` is the distribution
    for the token FOLLOWING tokens[b, j]; ``last_logits[b]`` is row b's
    logits at its last valid position (what decode rows and
    prompt-completing prefill rows sample from). ``row_logits`` pulls one
    row to host lazily — verify rows need the full chain, everyone else
    only samples from ``last_logits``."""

    logits: jax.Array          # [B, S, V(, nc x V for codebooks)]
    last_logits: jax.Array     # [B, V] / [B, nc, V]
    # grouped steps: ``logits`` is the decode group's [B, 1, V]; the
    # prefill group's [P, C, V] and the slot of each of its rows
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
        # how every width-1 group (decode ticks, the decode group of a
        # grouped tick, bursts) reads the pool: "pool" attends over the
        # whole leaf, "gather" gathers every table column, "kernel" is
        # the flash kernel's table walk. Fixed by the static shapes
        # (attention.reads_pool), under the mesh scope the steps are
        # traced in.
        with shd.activation_sharding_scope(mesh, self.policy) \
                if mesh is not None else contextlib.nullcontext():
            pool = attention.reads_pool(
                cfg, scfg.max_batch, scfg.blocks_per_seq, scfg.pool_blocks,
                scfg.block_size, scfg.attn_backend)
        self.kv_read = "kernel" if scfg.attn_backend == "flash" \
            else "pool" if pool else "gather"
        self.buckets = sorted({1, scfg.prefill_chunk}
                              | ({scfg.spec.k_max + 1}
                                 if scfg.spec is not None else set()))
        self.prefill_buckets = prefill_buckets(scfg.max_batch)
        self._fns: Dict[tuple, callable] = {}
        self._grouped_built = False
        self.n_steps = 0            # device steps dispatched (async
        #                             engines add burst iterations on top)

    # --- batch construction ------------------------------------------------
    def width_for(self, max_valid: int) -> int:
        """Smallest compiled bucket covering ``max_valid`` tokens/row."""
        for b in self.buckets:
            if b >= max_valid:
                return b
        self.buckets.append(max_valid)      # rare: register a new bucket
        self.buckets.sort()
        return max_valid

    def prefill_bucket_for(self, n_prefill: int) -> int:
        """Smallest prefill-group bucket holding ``n_prefill`` rows."""
        return next(p for p in self.prefill_buckets if p >= n_prefill)

    def new_batch(self, max_valid: int, tables: np.ndarray,
                  n_prefill: int = 0) -> StepBatch:
        """An empty batch for rows of at most ``max_valid`` tokens. With
        ``n_prefill`` > 0 (the tick's prefill rows, when it has no verify
        row) the batch is grouped: chunk-wide, decode rows at width 1."""
        if n_prefill:
            return StepBatch.empty(
                self.scfg.max_batch, self.scfg.prefill_chunk, tables,
                n_codebooks=self.cfg.n_codebooks,
                prefill_bucket=self.prefill_bucket_for(n_prefill))
        return StepBatch.empty(self.scfg.max_batch,
                               self.width_for(max_valid), tables,
                               n_codebooks=self.cfg.n_codebooks)

    # --- the one step ------------------------------------------------------
    def _fn(self, width: int, has_prefill: bool, prefill_bucket: int = 0):
        """One jit per step shape, named for it: ``serve_step_w1``,
        ``serve_step_w32_prefill`` (every row at width 32), or
        ``serve_step_w1_prefill_p4x32`` (a grouped tick: decode rows at
        width 1, four prefill rows of 32). A compile event and the
        profiler's dispatch span say which shape ran. No-prefill ticks —
        the serving steady state — compile to the pure sparse decode FFN
        and never stream the dense W_down."""
        key = (width, has_prefill, prefill_bucket)
        fn = self._fns.get(key)
        if fn is None:
            mdl, bs = self.model, self.scfg.block_size
            backend = self.scfg.attn_backend
            mesh, policy = self.mesh, self.policy
            if prefill_bucket:
                name = f"serve_step_w1_prefill_p{prefill_bucket}x{width}"
                run = grouped_step(mdl, bs, backend)
            else:
                name = f"serve_step_w{width}" + ("_prefill" if has_prefill
                                                 else "")
                run = uniform_step(mdl, bs, backend, has_prefill)
            if mesh is None:
                # shared across runner instances
                gkey = (mdl.cfg, bs, backend) + key
                fn = _JIT_CACHE.get(gkey)
                if fn is not None:
                    self._fns[key] = fn
                    return fn

            if mesh is not None:
                # trace under the activation-sharding scope (model code
                # consults it for the seq-sharded LSE decode path) and pin
                # the cache's layout so it never drifts across ticks;
                # logits come back replicated — sampling and the verify
                # chain read them host-side, as do the routing counts.
                base = run

                def run(*args):
                    with shd.activation_sharding_scope(mesh, policy):
                        return base(*args)

                run.__name__ = run.__qualname__ = name
                n_logits = 3 if prefill_bucket else 2
                fn = jax.jit(run, out_shardings=(
                    (self._repl,) * n_logits
                    + (self._cache_shardings, self._repl)))
            else:
                run.__name__ = run.__qualname__ = name
                fn = jax.jit(run)
                _JIT_CACHE[gkey] = fn
            self._fns[key] = fn
        return fn

    def _build_grouped(self) -> None:
        """Build the grouped executable of every prefill bucket, each run
        once on an all-idle batch (every write drops at the sentinel, the
        pool is left as it was): the first tick with a prefill row pays
        for all of them, and a later tick with more prefill rows compiles
        nothing."""
        B, C = self.scfg.max_batch, self.scfg.prefill_chunk
        nc = self.cfg.n_codebooks
        n_blocks = jax.tree.leaves(self.cache["units"])[0].shape[1]
        cache = dict(self.cache, block_tables=jnp.full_like(
            self.cache["block_tables"], n_blocks))
        for P in self.prefill_buckets:
            dec = np.zeros((B, 1, nc) if nc else (B, 1), np.int32)
            pf = np.zeros((P, C, nc) if nc else (P, C), np.int32)
            out = self._fn(C, True, P)(
                self.params, jnp.asarray(dec), jnp.asarray(pf), cache,
                jnp.zeros((B,), jnp.int32), jnp.full((P,), B, jnp.int32),
                jnp.zeros((P,), jnp.int32), jnp.zeros((P,), jnp.int32))
            jax.block_until_ready(out)
            del out
        self._grouped_built = True

    def step(self, batch: StepBatch, fence: bool = True,
             tokens=None) -> StepOutput:
        """Run one unified step: republish host-truth lens/tables, execute
        the bucketed jit, return per-position and last-valid logits.

        ``fence=False`` is the async engine's double-buffered dispatch
        (docs/async.md): even under tracing with ``fence_device`` on, the
        call returns as soon as the step is dispatched — the engine
        reconciles the results one tick later, attributing the deferred
        wait to its sample_sync span instead. ``tokens`` overrides
        ``batch.tokens`` with a DEVICE array (same [B, S] shape), letting
        tick t+1's input chain on tick t's still-in-flight sampled tokens
        without a host round-trip."""
        width = batch.tokens.shape[1]
        has_prefill = bool(np.any(batch.phase == PREFILL))
        P = batch.prefill_bucket
        if P and not self._grouped_built:
            self._build_grouped()
        tr = self.tracer
        attrs = {"prefill_bucket": P} if P else {}
        pf_slots = pf_logits = None
        with tr.span("device_dispatch", width=width,
                     has_prefill=has_prefill, **attrs):
            self.cache["lens"] = jnp.asarray(batch.row_start)
            self.cache["block_tables"] = jnp.asarray(batch.tables)
            if P:
                pf_slots = np.flatnonzero(batch.phase == PREFILL)
                dec, pf, *rows = _grouped_args(batch, pf_slots)
                logits, pf_logits, last, self.cache, counts = self._fn(
                    width, True, P)(self.params, dec, pf, self.cache, *rows)
            else:
                toks = jnp.asarray(batch.tokens) if tokens is None \
                    else tokens
                logits, last, self.cache, counts = self._fn(
                    width, has_prefill)(
                    self.params, toks, self.cache,
                    jnp.asarray(batch.n_valid),
                    jnp.asarray(batch.phase == PREFILL))
        self.n_steps += 1
        if fence and tr.enabled and tr.cfg.fence_device:
            # fence so device_wait covers actual execution, not just
            # dispatch — host/device attribution depends on this; the
            # untraced path never blocks (async dispatch preserved)
            with tr.span("device_wait"):
                jax.block_until_ready((logits, last))
        return StepOutput(logits=logits, last_logits=last,
                          prefill_logits=pf_logits, prefill_slots=pf_slots,
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
           "grouped_step", "prefill_buckets", "uniform_step"]
