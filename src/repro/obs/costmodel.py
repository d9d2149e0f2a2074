"""Static cost extraction from the compiled serving step (per bucket).

The serving profiler (obs.profile) answers "how close is each width
bucket to the hardware roofline"; this module supplies the numerator:
FLOPs and bytes the compiled ``ModelRunner.step`` executable performs,
total and attributed per ``jax.named_scope`` (the model annotates
"attn", "ffn_dense", "ffn_sparse", "logits"; the sampler executable is
the "sample" scope).

Two sources, cross-checked:

  * ``lower().compile().cost_analysis()`` — XLA's own totals ("flops",
    "bytes accessed"). Exact for the executable it describes, but a
    ``while``-loop body (the unit scan) is counted ONCE regardless of
    trip count, so the serving executable's numbers would undercount
    the stack n_units-fold.
  * HLO-text parsing of ``compile().as_text()`` — every ``dot`` op
    carries its output shape, its operands' names (whose defining
    instructions carry their shapes), contracting dims, and an
    ``op_name`` metadata path in which ``jax.named_scope`` names
    survive. dot FLOPs = 2 * prod(output dims) * prod(contracting
    dims); attribution = the scope segment of the op_name path.

Both sources therefore run against an UNROLLED twin of the step
(``dataclasses.replace(cfg, unroll=True)`` — transformer._paged_stack's
loop-free branch, same math and cache layout): the totals become exact
and every unit's dots appear individually in the text. The twin is the
runner's own step function (runner.step_fn) lowered on the runner's own
abstract arguments (ModelRunner.step_args), once per (W, P) step shape
and only when profiling is on (ObsConfig.profile); serving executables
are untouched.

The per-scope split covers dot (matmul/einsum) cost only — elementwise
ops, norms, gathers land in "other" (total minus attributed). Tier-1
asserts the attributed share stays within 5% of the executable total
for the NeCTAr config: the serving step is matmul-dominated, which is
the whole premise of judging it against a roofline.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict

import jax
import jax.numpy as jnp

# scope names the model annotates (models/transformer.py,
# models/ffn.py); "sample" is the sampler executable, "other" is the
# unattributed remainder
SCOPES = ("attn", "ffn_dense", "ffn_sparse", "moe_router", "moe_experts",
          "logits", "sample")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# "%dot.4 = f32[4,128]{1,0} dot(%x.1, %w.1), ..." — the defined name, its
# dtype and dims (layout suffix ignored); operands are referenced by name
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]")
_DOT_OPERANDS_RE = re.compile(r"\sdot\(([^)]*)\)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def _dims(s: str) -> tuple:
    return tuple(int(d) for d in s.split(",")) if s else ()


def _prod(dims) -> int:
    out = 1
    for d in dims:
        out *= int(d)
    return out


def scope_of(op_name: str) -> str:
    """Map an HLO op_name metadata path to its named_scope attribution:
    the first path segment that is a known scope name ("jit(run)/
    jit(main)/attn/.../dot_general" -> "attn"), else "other"."""
    for seg in op_name.split("/"):
        if seg in SCOPES:
            return seg
    return "other"


def parse_hlo_dot_costs(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-scope {"flops", "bytes"} summed over every ``dot`` op in the
    optimized HLO text. Bytes are the dot's operand + output footprint
    (the traffic a roofline charges the op, ignoring fusion reuse — an
    upper bound consistent with XLA's "bytes accessed" convention).

    A dot line names its operands (``dot(%x.1, %w.1)``) without their
    shapes, so each operand's shape is read from the instruction that
    defines it. Names are scoped to their computation (every fused
    computation has its own ``%param_0``), so the table restarts at
    each computation header."""
    out: Dict[str, Dict[str, float]] = {}
    shapes: Dict[str, tuple] = {}
    for line in hlo_text.splitlines():
        d = _DEF_RE.match(line)
        if d is None:
            if line.rstrip().endswith("{"):       # computation header
                shapes = {}
            continue
        res = (d.group(2), _dims(d.group(3)))
        shapes[d.group(1)] = res
        ops = _DOT_OPERANDS_RE.search(line)
        if ops is None:
            continue
        names = _OPERAND_RE.findall(ops.group(1))
        if len(names) != 2 or not all(n in shapes for n in names):
            continue
        lhs, rhs = shapes[names[0]], shapes[names[1]]
        contract = _LHS_CONTRACT_RE.search(line)
        cdims = _dims(contract.group(1)) if contract else ()
        contracted = _prod(lhs[1][i] for i in cdims)
        flops = 2.0 * _prod(res[1]) * contracted
        byts = float(sum(_prod(dims) * _DTYPE_BYTES.get(dt, 4)
                         for dt, dims in (res, lhs, rhs)))
        m = _OP_NAME_RE.search(line)
        scope = scope_of(m.group(1)) if m else "other"
        acc = out.setdefault(scope, {"flops": 0.0, "bytes": 0.0})
        acc["flops"] += flops
        acc["bytes"] += byts
    return out


def executable_totals(compiled) -> Dict[str, float]:
    """{"flops", "bytes"} from ``compiled.cost_analysis()``."""
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


@dataclasses.dataclass
class StepCost:
    """Static cost of ONE execution of a (width, prefill_bucket) step
    shape: XLA totals plus the per-named_scope dot split ("other" holds
    the unattributed remainder, floored at 0 — the split always sums to
    the total by construction, and ``attributed_frac`` reports how much
    of it the scoped dots genuinely cover)."""

    width: int
    prefill_bucket: int
    flops: float
    hbm_bytes: float
    by_scope: Dict[str, Dict[str, float]]

    @property
    def attributed_frac(self) -> float:
        scoped = sum(v["flops"] for k, v in self.by_scope.items()
                     if k != "other")
        return scoped / self.flops if self.flops else 0.0


def _sds(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def step_cost(runner, width: int, prefill_bucket: int = 0) -> StepCost:
    """Lower + compile the unrolled twin of ``runner``'s step of shape
    (W, P) from abstract args (no device work beyond the compile) and
    extract its static cost."""
    from repro.serve.runner import step_fn
    scfg = runner.scfg
    twin = type(runner.model)(dataclasses.replace(runner.cfg, unroll=True))
    toks, pf, n_valid, *rows = runner.step_args(
        width, prefill_bucket, jax.ShapeDtypeStruct)
    compiled = jax.jit(step_fn(twin, scfg.block_size,
                               scfg.attn_backend)).lower(
        _sds(runner.params), toks, pf, _sds(runner.cache), n_valid,
        *rows).compile()
    totals = executable_totals(compiled)
    by_scope = parse_hlo_dot_costs(compiled.as_text())
    attributed = sum(v["flops"] for v in by_scope.values())
    attr_bytes = sum(v["bytes"] for v in by_scope.values())
    # "other" already holds dots outside any named scope; ADD the
    # non-dot remainder (elementwise/norm/gather ops) so the full split
    # sums exactly to the executable totals
    other = by_scope.setdefault("other", {"flops": 0.0, "bytes": 0.0})
    other["flops"] += max(totals["flops"] - attributed, 0.0)
    other["bytes"] += max(totals["bytes"] - attr_bytes, 0.0)
    return StepCost(width=width, prefill_bucket=prefill_bucket,
                    flops=totals["flops"], hbm_bytes=totals["bytes"],
                    by_scope=by_scope)


def sampler_cost(batch: int, vocab: int, n_codebooks: int = 0,
                 ) -> Dict[str, float]:
    """Static cost of the per-tick sampling executable (the "sample"
    scope). Profiled as the greedy argmax kernel — the serving steady
    state and the equivalence-test path; the filtered sampler costs
    more, which the attainment table notes rather than models. A
    codebook stream samples ``n_codebooks`` vocab rows per request: the
    same argmax over ``batch * n_codebooks`` rows."""
    from repro.serve.sampling import _greedy_batch
    rows = batch * max(n_codebooks, 1)
    compiled = jax.jit(_greedy_batch).lower(
        jax.ShapeDtypeStruct((rows, vocab), jnp.float32)).compile()
    return executable_totals(compiled)


__all__ = ["SCOPES", "StepCost", "executable_totals",
           "parse_hlo_dot_costs", "sampler_cost", "scope_of",
           "step_cost"]
