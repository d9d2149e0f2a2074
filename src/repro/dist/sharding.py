"""Mesh shardings + activation-sharding constraints.

Axis convention (launch.mesh): ``pod`` and ``data`` are batch axes,
``model`` is the tensor/sequence-parallel axis. Policy knobs:

  * fsdp           — shard params across the data axis too (ZeRO-3-style)
  * seq_shard      — Megatron-SP: residuals sharded over seq on 'model'
  * pod_param_shard— extend fsdp across the pod axis (400B-class models)
  * shard_kv_seq   — decode KV cache sharded over seq on 'model'

``constrain_*`` are identity unless an ``activation_sharding_scope`` is
active, so model code calls them unconditionally; single-device tests and
the serving engine pay nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BATCH_AXIS_NAMES = ("pod", "data")
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    fsdp: bool = False
    seq_shard: bool = False
    pod_param_shard: bool = False
    shard_kv_seq: bool = False
    # Bit-reproducible tensor parallelism (sharded serving): shard every
    # weight on its OUTPUT dim and all-gather activations at the
    # constrain_tp_exact points, so every collective is a CONCATENATION
    # (order-preserving) and never a summation — fp accumulation order
    # matches the single device exactly, which is what keeps greedy
    # decode token-identical even through int8 KV quantization rounding
    # (a psum's ~1e-7 reduction-order noise amplifies to a full
    # quantization step when it lands on a rounding boundary).
    exact_tp: bool = False


def _batch_axes(mesh: Mesh, batch: int) -> Tuple[str, ...]:
    """The batch axes of ``mesh`` whose combined size divides ``batch``."""
    axes, n = [], 1
    for a in BATCH_AXIS_NAMES:
        if a in mesh.axis_names and batch % (n * mesh.shape[a]) == 0:
            axes.append(a)
            n *= mesh.shape[a]
    return tuple(axes)


def _model_size(mesh: Mesh) -> int:
    return mesh.shape[MODEL_AXIS] if MODEL_AXIS in mesh.axis_names else 1


# ---------------------------------------------------------------------------
# Parameter / input / cache shardings


def _param_spec(shape, mesh: Mesh, policy: ShardingPolicy):
    """Tensor-parallel on 'model' over the largest divisible trailing dim;
    fsdp shards one remaining dim over the data (and optionally pod) axes.
    Stacked-unit leaves keep axis 0 (the unit axis) replicated — it is the
    scan axis.

    ``policy.exact_tp`` shards ONLY the last dim — for matmul weights
    that is the OUTPUT dim, so a replicated activation times a
    column-sharded weight needs no cross-device reduction (the
    bit-reproducible serving layout; see constrain_tp_exact). A leaf
    whose last dim doesn't divide stays fully REPLICATED — falling back
    to an earlier (contraction) dim would silently reintroduce the psum
    the layout exists to avoid."""
    spec = [None] * len(shape)
    msize = _model_size(mesh)
    lo = 1 if len(shape) >= 3 else 0  # skip the [U, ...] stack axis
    if msize > 1 and len(shape) >= 2:
        if policy.exact_tp:
            cands = [len(shape) - 1]
        else:
            cands = sorted(range(lo, len(shape)),
                           key=lambda i: shape[i], reverse=True)
        for i in cands:
            if shape[i] % msize == 0 and shape[i] >= msize:
                spec[i] = MODEL_AXIS
                break
    if policy.fsdp:
        axes = tuple(a for a in BATCH_AXIS_NAMES if a in mesh.axis_names)
        if not policy.pod_param_shard:
            axes = axes[-1:]
        dsize = 1
        for a in axes:
            dsize *= mesh.shape[a]
        if dsize > 1:
            for i in range(lo, len(shape)):
                if spec[i] is None and shape[i] % dsize == 0 \
                        and shape[i] >= dsize:
                    spec[i] = axes if len(axes) > 1 else axes[0]
                    break
    return P(*spec)


def params_shardings(params_shapes, cfg, mesh: Mesh,
                     policy: Optional[ShardingPolicy] = None):
    """NamedSharding tree for a param tree (arrays or ShapeDtypeStructs).

    Under ``policy.exact_tp`` a leaf that is a vector per layer (an
    RMS-norm scale, a bias; ``[U, d]`` under the stacked ``units``)
    stays replicated: a norm scale sharded on d lets GSPMD compute the
    norm's mean of squares per shard and psum it, which reorders the
    sum and, in bf16, flips ulps of the residual stream."""
    policy = policy or ShardingPolicy()

    def one(path, leaf):
        stacked = getattr(path[0], "key", None) == "units"
        if policy.exact_tp and len(leaf.shape) - stacked <= 1:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _param_spec(leaf.shape, mesh, policy))

    return jax.tree_util.tree_map_with_path(one, params_shapes)


def batch_shardings(cfg, mesh: Mesh, b: int, s: int, kind: str,
                    policy: Optional[ShardingPolicy] = None):
    """Shardings for every possible model-input key (callers filter)."""
    bt = _batch_axes(mesh, b)
    lead = NamedSharding(mesh, P(bt) if bt else P())
    return {
        "tokens": lead,
        "labels": lead,
        "mask": lead,
        "vision_embeds": lead,
        "audio_embeds": lead,
        "mrope_positions": NamedSharding(mesh, P(None, bt) if bt else P()),
    }


def cache_shardings(cfg, mesh: Mesh, batch: int,
                    policy: Optional[ShardingPolicy] = None,
                    paged: bool = False):
    """Returns fn(path, leaf) -> NamedSharding for tree_map_with_path over a
    decode cache.

    Contiguous cache (``paged=False``, {"lens": [B], "units": {bj: leaf
    [U, B, ...]}}): batch-sharded over the batch axes; with
    ``policy.shard_kv_seq`` the K/V seq axis additionally shards over
    'model' (the LSE-combine decode layout).

    Paged block pool (``paged=True``, {"lens": [B], "block_tables":
    [B, MB], "units": {bj: k/v [U, n_blocks, bs, Kv, Dh] (+ _scale
    leaves)}}): the KV-HEAD axis shards over 'model' — the tensor-
    parallel partition matching column-sharded wk/wv, so each device
    writes and reads only its local heads of every block. The BLOCK axis
    is never sharded: block tables address arbitrary physical blocks, so
    every device must hold (its head slice of) every block — that is what
    keeps allocation, refcounts, COW and defrag host-side and
    shard-agnostic. lens/block_tables are replicated host-truth,
    republished by the runner every step."""
    bt = _batch_axes(mesh, batch)
    msize = _model_size(mesh)
    kv_seq = bool(policy and policy.shard_kv_seq) and msize > 1

    def fn(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        last = names[-1] if names else None
        if paged:
            if last in ("k", "v", "k_scale", "v_scale") and leaf.ndim >= 5 \
                    and msize > 1 and leaf.shape[3] % msize == 0 \
                    and leaf.shape[3] >= msize:
                spec = [None] * leaf.ndim
                spec[3] = MODEL_AXIS        # [U, nb, bs, Kv, Dh|1]
                return NamedSharding(mesh, P(*spec))
            return NamedSharding(mesh, P())
        if last in ("lens", "block_tables"):
            return NamedSharding(mesh, P(bt) if bt else P())
        if leaf.ndim >= 2 and bt:
            spec = [None] * leaf.ndim
            spec[1] = bt
            if kv_seq and leaf.ndim >= 3 and last in ("k", "v") \
                    and leaf.shape[2] % msize == 0:
                spec[2] = MODEL_AXIS
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return fn


# ---------------------------------------------------------------------------
# Activation-sharding scope (used by dryrun lowering; identity otherwise)

_SCOPE: Optional[Tuple[Mesh, ShardingPolicy]] = None


def current_scope() -> Optional[Tuple[Mesh, ShardingPolicy]]:
    """The active (mesh, policy) activation-sharding scope, or None.

    Model code consults this at TRACE time to pick sharded code paths
    (e.g. attention.attn_step_grouped routes single-token decode through
    the LSE-combine collective when policy.shard_kv_seq) — the scope is a
    host-side global, so whatever is active while jit traces is what the
    compiled program bakes in."""
    return _SCOPE


@contextlib.contextmanager
def activation_sharding_scope(mesh: Mesh, policy: ShardingPolicy):
    global _SCOPE
    prev = _SCOPE
    _SCOPE = (mesh, policy)
    try:
        yield
    finally:
        _SCOPE = prev


def _constrain(x, spec_fn):
    if _SCOPE is None:
        return x
    mesh, policy = _SCOPE
    spec = spec_fn(mesh, policy, x)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_residual(x):
    """[B, S, d] residual: batch-sharded; seq-sharded over 'model' when the
    policy asks for Megatron-SP residuals (saves the scan-boundary HBM)."""

    def spec(mesh, policy, x):
        bt = _batch_axes(mesh, x.shape[0])
        seq = None
        if policy.seq_shard and x.ndim >= 3 \
                and x.shape[1] % _model_size(mesh) == 0 \
                and x.shape[1] >= _model_size(mesh) > 1:
            seq = MODEL_AXIS
        if not bt and seq is None:
            return None
        return P(bt if bt else None, seq)

    return _constrain(x, spec)


def constrain_seq_gathered(x):
    """[B, S, d] activation entering a tensor-parallel matmul: sequence must
    be gathered (replicated over 'model'); batch stays sharded."""

    def spec(mesh, policy, x):
        bt = _batch_axes(mesh, x.shape[0])
        return P(bt) if bt else None

    return _constrain(x, spec)


def constrain_tp_exact(x):
    """All-gather point of the bit-reproducible serving layout
    (ShardingPolicy.exact_tp): force ``x`` fully replicated. Placed right
    after each output-dim-sharded matmul (and after the embedding
    gather), it turns the layout's only collectives into all-gathers —
    concatenations preserve every fp value bit-exactly, while a psum of
    partial products re-orders the accumulation and perturbs the last
    ulp. Identity off-scope and under non-exact policies, so model code
    calls it unconditionally."""

    def spec(mesh, policy, x):
        if not policy.exact_tp or _model_size(mesh) <= 1:
            return None
        return P()

    return _constrain(x, spec)


def constrain_moe_dispatch(t):
    """[E, cap, ...] expert-parallel dispatch: experts over 'model'."""

    def spec(mesh, policy, t):
        if _model_size(mesh) > 1 and t.shape[0] % _model_size(mesh) == 0:
            return P(MODEL_AXIS)
        return None

    return _constrain(t, spec)
