"""Config system — the 'Chipyard parameter system' of this framework.

One frozen dataclass describes any member of the supported model family
(dense / GQA / MQA transformers, MoE, VLM backbone, hybrid SSM, audio
decoder, xLSTM). Architectures are generated from configs exactly the way
NeCTAr generates SoC variants from Chipyard parameters (paper §III).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | vlm | hybrid | audio | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    d_head: int = 0                 # 0 -> d_model // n_heads
    act: str = "silu"
    glu: bool = True
    qk_norm: bool = False
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    mrope: bool = False             # qwen2-vl M-RoPE (3 position channels)
    pos_emb: str = "rope"           # rope | sin | none

    # --- latent attention (MLA, DeepSeek-V2/V3): kv_lora_rank > 0 caches
    # one RMS-normed latent of that width plus one shared roped key of
    # qk_rope_head_dim per token; d_head is then qk_nope + qk_rope ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE (d_ff is the routed expert's width) ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    first_k_dense_replace: int = 0  # leading dense layers before the MoE
    dense_d_ff: int = 0             # their FFN width (intermediate_size)
    scoring_func: str = "softmax"   # router scores: softmax | sigmoid
    router_bias: bool = False       # noaux_tc: a bias for selection only
    norm_topk_prob: bool = True     # weights renormalised over the top-k
    routed_scaling_factor: float = 1.0
    # experts [lo, hi) held on this chip (expert parallelism); (0, 0) = all
    held_experts: Tuple[int, int] = (0, 0)

    # --- hybrid / ssm ---
    ssm_state: int = 0
    ssm_heads: int = 0              # 0 -> d_model*expand // 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    block_pattern: Tuple[str, ...] = ()   # e.g. ("mamba2",)*5 + ("shared_attn",)
    slstm_every: int = 0            # xlstm: every k-th block is sLSTM

    # --- audio / vlm frontends (stubs per assignment) ---
    n_codebooks: int = 0            # musicgen: EnCodec streams
    frontend: str = "none"          # none | vision_stub | audio_stub

    # --- the paper's technique ---
    relu_sparse: bool = False       # ReLU-fied FFN + sparse decode path
    sparse_k_frac: float = 0.125    # active fraction for top-k gather
    int8_weights: bool = False      # NMCE int8 weight path at decode
    predictor_rank: int = 0         # 0 = oracle top-k; >0 = Deja-Vu predictor

    # --- numerics ---
    dtype: str = "bfloat16"
    remat: bool = True
    # lower-triangle-only attention schedule (~2x fewer causal FLOPs);
    # the perf-loop variant — off by default for the paper-faithful baseline
    block_causal: bool = False
    # unroll every lax.scan (layers, KV blocks, SSD chunks, loss chunks).
    # Used by the dry-run cost probes: XLA's HloCostAnalysis counts a while
    # body ONCE regardless of trip count, so exact FLOPs/collective-bytes
    # need loop-free HLO (launch.dryrun lowers small unrolled probes).
    unroll: bool = False

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.qk_nope_head_dim
                               + self.qk_rope_head_dim if self.mla
                               else self.d_model // self.n_heads)
        assert self.n_heads % max(self.n_kv_heads, 1) == 0, \
            f"{self.name}: n_heads must be a multiple of n_kv_heads"

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def rope_dim(self) -> int:
        """Width each rotary table covers: the shared roped key under
        MLA, the whole head otherwise."""
        return self.qk_rope_head_dim if self.mla else self.d_head

    @property
    def held_range(self) -> Tuple[int, int]:
        """Experts [lo, hi) whose weights this chip holds."""
        lo, hi = self.held_experts
        return (lo, hi) if hi else (0, self.n_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held_range
        return hi - lo

    @property
    def is_recurrent(self) -> bool:
        """True if decode state is O(1) in context length (SSM/xLSTM)."""
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode: SSM and hybrid archs run long_500k."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + norms)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        n_q = self.n_heads * self.d_head
        n_kv = self.n_kv_heads * self.d_head
        emb = v * d * (1 + self.n_codebooks if self.n_codebooks else 1)
        head = 0 if self.tie_embeddings else v * d * (self.n_codebooks or 1)
        total = emb + head + d  # final norm
        attn = n_q * d + 2 * n_kv * d + n_q * d
        if self.mla:
            r, rd = self.kv_lora_rank, self.qk_rope_head_dim
            h_qk = self.n_heads * (self.qk_nope_head_dim + rd)
            h_v = self.n_heads * self.v_head_dim
            attn = d * h_qk + d * (r + rd) + r \
                + r * (h_qk - self.n_heads * rd + h_v) + h_v * d
        for i, blk in enumerate(self.layer_kinds()):
            if blk in ("attn", "shared_attn"):
                total += attn + d  # qkvo + norm
                if blk == "attn":
                    lead = i < self.first_k_dense_replace
                    total += self._ffn_params(
                        self.dense_d_ff if lead else 0) + d
            elif blk == "mamba2":
                di = self.ssm_expand * d
                heads = self.ssm_heads or di // 64
                total += d * (2 * di + 2 * self.ssm_state + heads)  # in_proj
                total += di * self.ssm_conv + di * d + 2 * heads + di + d
            elif blk in ("mlstm", "slstm"):
                di = 2 * d
                total += d * 2 * di + di * d + 4 * di * 2 + d  # projs + gates
            elif blk == "moe":
                total += attn + d
                total += d * self.n_experts  # router
                total += self.n_experts if self.router_bias else 0
                e_f = (2 if self.glu else 1) * d * f + f * d
                total += self.n_held * e_f + self.n_shared_experts * e_f + d
        # shared_attn params are counted once (they are shared)
        n_shared = sum(1 for b in self.layer_kinds() if b == "shared_attn")
        if n_shared > 1:
            total -= (n_shared - 1) * (n_q * d + 2 * n_kv * d + n_q * d + d)
        return int(total)

    def _ffn_params(self, d_ff: int = 0) -> int:
        f = d_ff or self.d_ff
        return (2 if self.glu else 1) * self.d_model * f + f * self.d_model

    def pattern_unit(self) -> Tuple[str, ...]:
        """The repeating block pattern; the stack scans over
        n_layers/len(unit) copies of this unit."""
        if self.block_pattern:
            return self.block_pattern
        if self.family == "moe":
            return ("moe",)
        if self.family == "ssm":
            if self.slstm_every:
                return ("mlstm",) * (self.slstm_every - 1) + ("slstm",)
            return ("mlstm",)
        return ("attn",)

    @property
    def n_units(self) -> int:
        """Copies of the pattern unit the stack scans, after the
        ``first_k_dense_replace`` leading dense layers."""
        unit = self.pattern_unit()
        n = self.n_layers - self.first_k_dense_replace
        assert n % len(unit) == 0, \
            f"{self.name}: n_layers {n} % unit {len(unit)} != 0"
        return n // len(unit)

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind, resolved from the pattern/family: the
        leading dense layers ("attn"), then the pattern unit repeated."""
        pat = self.pattern_unit()
        lead = self.first_k_dense_replace
        return ("attn",) * lead + tuple(
            pat[i % len(pat)] for i in range(self.n_layers - lead))


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    microbatch: int = 0             # 0 = no accumulation
    adam_8bit: bool = False         # int8 moments (blockwise scales)
    grad_compression: str = "none"  # none | int8_ef (cross-pod)
    checkpoint_every: int = 200
    keep_checkpoints: int = 3
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative decoding (repro.spec): draft K tokens cheaply, verify
    them in ONE batched forward pass of the target model — amortizing one
    weight-stream read (the paper's decode bottleneck, Table II) over up
    to K+1 emitted tokens."""

    drafter: str = "ngram"          # ngram | model | selfspec
    k: int = 4                      # initial draft length per verify step
    k_min: int = 1                  # adaptive-K floor
    k_max: int = 8                  # adaptive-K ceiling (fixed verify shape)
    adaptive: bool = True           # back off K when acceptance drops
    accept_low: float = 0.4         # EMA acceptance below this shrinks K
    accept_high: float = 0.7        # EMA acceptance above this grows K
    ema_decay: float = 0.9          # acceptance-rate EMA decay
    temperature: float = 0.0        # 0 = greedy accept; >0 rejection sampling
    ngram: int = 3                  # prompt-lookup: longest suffix match
    draft_name: str = ""            # registry config for drafter="model"
    draft_frac: float = 0.0625      # selfspec: sparse active fraction
    predictor_rank: int = 16        # selfspec: Deja-Vu predictor rank
    seed: int = 0                   # acceptance/draft sampling RNG


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Tracing & telemetry (repro.obs).

    Disabled by default: with ``enabled=False`` every instrumentation
    hook in the engine/runner resolves to a shared no-op singleton
    (repro.obs.trace.NULL_TRACER) — no span objects are allocated, no
    device fences are inserted, and the hot tick path pays only a
    handful of no-op attribute calls (asserted < 2% of tick time in
    tier-1).

    With ``enabled=True`` the engine records:

      * per-tick PHASE SPANS (schedule -> draft -> batch-assemble ->
        device-dispatch -> device-wait -> sample-sync -> postprocess),
        with ``block_until_ready`` fencing between dispatch and wait so
        host-overhead-per-tick and device-time-per-tick are separately
        attributable, plus per-row-kind (prefill/decode/verify) and
        padding-waste breakdowns per tick;
      * per-request LIFECYCLE EVENTS (arrival, admission, prefix hit,
        prefill chunks, first token, preemption/replay, spec
        verify/rollback, COW, finish) — one timeline per request.

    Exporters (repro.obs.export): Chrome-trace/Perfetto JSON, JSONL
    structured event log, Prometheus text (the metrics registry is
    always live, tracing on or off)."""

    enabled: bool = False
    profile: bool = False           # roofline attainment profiling
    #                                 (obs.profile): per-width-bucket
    #                                 static cost (compiled-executable
    #                                 FLOPs/bytes, per-named_scope) joined
    #                                 with measured device_wait time.
    #                                 Implies enabled (needs the fenced
    #                                 tick spans); off the hot path — the
    #                                 cost twin compiles lazily per bucket.
    tick_spans: bool = True         # per-tick phase spans
    timeline: bool = True           # per-request lifecycle events
    fence_device: bool = True       # block_until_ready between dispatch
    #                                 and wait (host/device attribution)
    jax_annotations: bool = False   # also emit jax.profiler
    #                                 TraceAnnotations per span (with its
    #                                 attributes) and one engine.step
    #                                 annotation per tick
    max_events: int = 262_144       # storage bound: spans+events beyond
    #                                 this are counted (dropped), not kept


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh for sharded serving (paged engine only).

    ``model`` is the tensor-parallel axis: transformer weights shard over
    it (dist.sharding.params_shardings) and the paged KV block pool
    partitions its KV-head axis over it (dist.sharding.cache_shardings
    with paged=True) — each device holds n_kv_heads/model heads of every
    physical block, so the pool's host-side bookkeeping (block tables,
    refcounts, COW, truncate, defrag, the radix prefix index) is
    completely shard-agnostic.

    ``shard_kv_seq`` additionally shards the gathered per-row KV
    *sequence* over ``model`` inside single-token decode attention and
    merges the per-shard partial softmaxes with the LSE-combine
    collective (dist.collectives.lse_combine_decode_attention) — the
    long-context layout where one device cannot hold a row's KV.

    ``data`` > 1 is reserved for batch-parallel replicas and is rejected
    by the engine until the runner actually batch-shards step inputs —
    accepting it today would silently replicate identical work across
    the extra devices.

    Declarative and jax-free: the engine materializes the actual
    jax.sharding.Mesh via launch.mesh.make_serving_mesh, so configs can
    be built before device state exists (e.g. under forced host-device
    counts)."""

    model: int = 1                  # tensor-parallel shards
    data: int = 1                   # batch-parallel replicas (reserved)
    shard_kv_seq: bool = False      # LSE-combine decode over seq shards

    @property
    def n_devices(self) -> int:
        return self.model * self.data


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Asynchronous engine tick pipeline (serve.engine async core).

    The synchronous engine runs every tick host-blocking: schedule ->
    dispatch -> wait -> sample -> postprocess. With ``enabled=True`` the
    engine overlaps host work with device compute two ways (docs/async.md):

      * DOUBLE-BUFFERED TICKS — tick t's device step is dispatched
        without ``block_until_ready``; tick t+1's StepBatch assembly and
        tick t-1's host bookkeeping (stop detection, streaming publish,
        radix publish, metrics) run while the device computes. The host
        reconciles tick t's sampled tokens one tick later.

      * DEVICE-RESIDENT DECODE LOOP — in the decode-only steady state
        (no waiting requests, no prefill, no spec, capacity for K more
        tokens per row) up to ``max_device_ticks`` decode steps run
        inside one ``lax.while_loop`` on device, early-exiting when every
        row hits a stop condition; the host syncs once per burst.

    Greedy output is token-identical to the synchronous engine — the
    differential fuzz harness (tests/test_async_differential.py) and the
    tier-1 identity tests assert it across plain/spec/prefix/int8/
    preemption regimes. Ticks that cannot preserve identity cheaply
    (prefill, spec, eviction pressure, penalized sampling) fall back to
    the synchronous path per-tick."""

    enabled: bool = False
    max_device_ticks: int = 8       # K: decode ticks per device burst (>=1)
    sync_every: int = 0             # force a host sync every N engine ticks
    #                                 (0 = only when the engine needs one);
    #                                 bounds streaming/metrics staleness


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_seq: int = 2048
    sparse_decode: bool = True      # use the NeCTAr sparse FFN path
    int8_decode: bool = True        # NMCE int8 weight streaming
    kv_quant: bool = False          # int8 KV cache

    # --- paged serving (serve.paged_kv + serve.scheduler) ---
    paged: bool = False             # block-table paged KV decode
    # radix-tree prefix cache (serve.prefix_cache): requests sharing a
    # prompt prefix share physical KV blocks (refcounted, copy-on-write);
    # admission prefills only the uncached suffix. Paged mode only.
    prefix_cache: bool = False
    block_size: int = 16            # tokens per KV block
    n_kv_blocks: int = 0            # KV pool size; 0 = max_batch*max_seq/bs
    prefill_chunk: int = 32         # chunked-prefill tokens per tick
    policy: str = "fifo"            # request ordering: fifo | priority
    max_queue: int = 256            # admission control: queue depth bound
    spec: Optional[SpecConfig] = None   # speculative decode (paged only)
    # attention read path for the unified runner step (serve.runner):
    # "naive" = reference gather through block tables (shardable);
    # "flash" = Pallas flash-decode kernel reading the block pools
    # directly via scalar-prefetched tables (single-token steps)
    attn_backend: str = "naive"
    # multi-device serving (paged + naive backend only): shard weights
    # and the KV block pool's head axis over the mesh's 'model' axis;
    # greedy output stays token-identical to the single-device engine
    mesh: Optional[MeshConfig] = None
    # tracing & telemetry (repro.obs): per-tick phase spans, request
    # lifecycle timelines, Perfetto/JSONL/Prometheus exporters. The
    # default is a no-op tracer; greedy output is token-identical
    # tracing on or off (tracing only observes, never schedules)
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    # asynchronous tick pipeline (docs/async.md): double-buffered host
    # loop + device-resident K-tick decode bursts. None = synchronous.
    # Paged mode only; greedy output stays token-identical async on/off.
    async_cfg: Optional[AsyncConfig] = None

    @property
    def blocks_per_seq(self) -> int:
        return -(-self.max_seq // self.block_size)

    @property
    def pool_blocks(self) -> int:
        return self.n_kv_blocks or self.max_batch * self.blocks_per_seq


@dataclasses.dataclass(frozen=True)
class DisaggConfig:
    """Disaggregated prefill/decode serving (serve.disagg).

    The two phases have opposite rooflines — prefill is compute-bound,
    decode weight-bandwidth-bound (the paper's near-core vs near-memory
    accelerator split) — so the DisaggCoordinator runs each on its own
    dedicated Engine and hands finished prefills over as a paged-KV
    block transfer. These knobs size the PREFILL engine relative to the
    decode engine's ServeConfig (which keeps the user-facing values):

    ``prefill_batch`` / ``prefill_blocks`` — the prefill engine's
    max_batch and KV pool size (0 = inherit the decode ServeConfig's).
    Prefill slots are transient (a request holds one only until
    handoff), so a small batch + a pool of a few in-flight prompts
    usually suffices and keeps the prefill tick cheap.

    ``direct_max_suffix`` — multi-turn fast path: when the DECODE
    engine's radix index already covers a prompt up to its last
    ``<= direct_max_suffix`` tokens, admission goes straight to the
    decode engine (the remaining suffix is at most one chunk of prefill
    there) instead of re-prefilling + re-copying blocks through a
    handoff. 0 disables decode-direct placement.

    Declarative and jax-free, like MeshConfig."""

    prefill_batch: int = 0          # prefill engine max_batch (0=inherit)
    prefill_blocks: int = 0         # prefill engine KV pool (0=inherit)
    direct_max_suffix: int = 0      # decode-direct if cached suffix <= this


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Multi-replica serving fleet (serve.fleet + serve.router).

    One ServeConfig builds every replica (homogeneous fleet); this adds
    the fleet-level knobs: replica count, routing policy, the router's
    own overflow queue (requests wait HERE when every replica's
    admission control is full, shed with FleetSaturated past the
    bound), and session stickiness for multi-turn traffic. Requires
    ``ServeConfig.paged`` — routing reads the paged scheduler's queue
    depth and the radix prefix index."""

    replicas: int = 1
    router_policy: str = "affinity"   # affinity | round_robin | least_loaded
    max_router_queue: int = 512       # bounded front-door overflow queue
    session_affinity: bool = True     # same session id -> same replica
    parallel_poll: bool = False       # tick replicas via a thread pool
    #                                   (serialized engines are the
    #                                   default: single-process fleets
    #                                   gain capacity, not CPU)


# --- assigned input shapes (seq_len, global_batch, kind) -------------------

SHAPES = {
    "train_4k": dict(seq=4096, batch=256, kind="train"),
    "prefill_32k": dict(seq=32768, batch=32, kind="prefill"),
    "decode_32k": dict(seq=32768, batch=128, kind="decode"),
    "long_500k": dict(seq=524288, batch=1, kind="decode"),
}


def applicable_shapes(cfg: ModelConfig) -> Tuple[str, ...]:
    """long_500k only for sub-quadratic archs (assignment rule)."""
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.supports_long_context:
        names.append("long_500k")
    return tuple(names)
