"""Sharded-serving checks (run under 4 fake CPU devices).

Invoked by test_mesh_serving.py in a subprocess so the forced device
count doesn't leak into the rest of the suite; argv[1] picks the check
group. Every check holds the sharded paged engine
(ServeConfig(mesh=MeshConfig(model=N))) to the PR's acceptance bar:
greedy output token-identical to the single-device engine — under plain
decode, speculation with rollback on shared prefixes, copy-on-write,
int8 KV, and the seq-sharded LSE-combine decode path — plus
metrics.summary() shard-consistency. Exits nonzero on any failure.
"""

import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS_EXTRA", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

from repro.configs import get_config  # noqa: E402
from repro.configs.base import (MeshConfig, ServeConfig,  # noqa: E402
                                SpecConfig)
from repro.models import Model  # noqa: E402
from repro.serve.engine import Engine  # noqa: E402
from repro.serve.scheduler import Request  # noqa: E402

CFG = get_config("nectar-relu-llama-1.7m")
PARAMS = Model(CFG).init(jax.random.PRNGKey(0))


def _prompts(lengths, seed=0, shared=0):
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(0, CFG.vocab, size=shared, dtype=np.int32)
    return [np.concatenate(
        [sys_p, rng.integers(0, CFG.vocab, size=int(n), dtype=np.int32)])
        for n in lengths]


def _engine(mesh=None, **kw):
    base = dict(max_batch=2, max_seq=96, paged=True, block_size=8,
                prefill_chunk=16, mesh=mesh)
    base.update(kw)
    return Engine(CFG, PARAMS, ServeConfig(**base))


def _serve(prompts, mesh=None, max_new=8, **kw):
    eng = _engine(mesh=mesh, **kw)
    done = eng.run([Request(rid=i, prompt=p, max_new=max_new)
                    for i, p in enumerate(prompts)], max_steps=3000)
    return {i: [int(t) for t in r.tokens_out] for i, r in done.items()}, eng


def _assert_shard_consistent(eng, model: int, kv_seq: bool = False):
    """metrics.summary() must report the mesh truthfully and the pool's
    per-shard byte gauges must tile exactly back to the global pool."""
    s = eng.metrics.summary()
    assert s["mesh"]["shape"]["model"] == model, s["mesh"]
    assert s["mesh"]["kv_pool_shards"] == eng.pool.model_shards
    assert s["mesh"]["shard_kv_seq"] == kv_seq
    pool = s["kv_pool"]
    assert pool["model_shards"] == eng.pool.model_shards
    assert pool["per_shard_capacity_bytes"] * pool["model_shards"] \
        == pool["capacity_bytes"]
    assert pool["per_shard_used_bytes"] * pool["model_shards"] \
        == pool["used_bytes"]
    # the device pool really is partitioned: each K/V leaf's sharding
    # splits the KV-head axis 'model'-ways
    leaf = jax.tree.leaves(eng.runner.cache["units"])[0]
    spec = leaf.sharding.spec
    assert spec[3] == "model", spec


def check_greedy(model: int, kv_seq: bool = False):
    """Plain paged greedy decode: model=N mesh == single device, and the
    summary gauges are shard-consistent (same work, partitioned bytes)."""
    prompts = _prompts([5, 23, 9, 14], seed=0)
    mesh = MeshConfig(model=model, shard_kv_seq=kv_seq)
    base, beng = _serve(prompts, max_batch=3, max_seq=64)
    out, eng = _serve(prompts, mesh=mesh, max_batch=3, max_seq=64)
    assert out == base, (base, out)
    _assert_shard_consistent(eng, model, kv_seq=kv_seq)
    bs, ss = beng.metrics.summary(), eng.metrics.summary()
    for key in ("generated_tokens", "decode_steps", "prefill_chunks"):
        assert bs[key] == ss[key], (key, bs[key], ss[key])
    assert bs["mesh"] == {}


def check_pool_read(model: int):
    """A pool smaller than max_batch x blocks_per_seq: width-1 groups
    read the whole head-sharded pool under GSPMD, token-identical to the
    single device and to the gathering default pool; under the kv-seq
    scope the gather (and the LSE combine) stays."""
    prompts = _prompts([5, 23, 9, 14], seed=0)
    kw = dict(max_batch=3, max_seq=64, n_kv_blocks=16)
    gathered, _ = _serve(prompts, max_batch=3, max_seq=64)
    base, beng = _serve(prompts, **kw)
    out, eng = _serve(prompts, mesh=MeshConfig(model=model), **kw)
    assert beng.runner.kv_read == eng.runner.kv_read == "pool"
    assert out == base == gathered, (gathered, base, out)
    _assert_shard_consistent(eng, model)
    seq = _engine(mesh=MeshConfig(model=model, shard_kv_seq=True), **kw)
    assert seq.runner.kv_read == "gather"


def check_spec_prefix(model: int):
    """Speculation (ngram drafter) + radix prefix cache on shared-prefix
    traffic: verify/rollback through SHARED blocks stays token-identical
    under sharding, and the cache actually hit."""
    prompts = _prompts([5, 9, 7], seed=1, shared=24)
    spec = SpecConfig(drafter="ngram", k=3, k_max=4)
    kw = dict(spec=spec, prefix_cache=True, max_new=10)
    base, _ = _serve(prompts, **kw)
    out, eng = _serve(prompts, mesh=MeshConfig(model=model), **kw)
    assert out == base, (base, out)
    s = eng.metrics.summary()
    assert s["spec_steps"] > 0
    assert s["prefix_hits"] >= 1
    _assert_shard_consistent(eng, model)


def check_cow(model: int):
    """Copy-on-write under sharding: force a sibling reference onto a
    running request's partial tail block mid-stream; its next write must
    COW (each device copying its local head slice), the shared block's
    sharded bytes must stay frozen, and output must be unchanged."""
    prompt = _prompts([10], seed=3)[0]

    def run(mesh, force_share):
        eng = _engine(mesh=mesh, prefix_cache=True, max_seq=64)
        eng.add_request(Request(rid=0, prompt=prompt, max_new=10))
        for _ in range(3):
            eng.step()
        frozen = None
        if force_share:
            e = next(iter(eng.sched.active.values()))
            assert e.ctx_len % 8 != 0           # mid-block frontier
            b = eng.pool.owned[e.slot][e.ctx_len // 8]
            eng.pool.share(1, [b])              # a "sibling" reader
            leaf = jax.tree.leaves(eng.runner.cache["units"])[0]
            frozen = (b, np.array(leaf[:, b]))
        while eng._busy():
            eng.step()
        toks = [int(t) for t in eng._requests[0].tokens_out]
        if force_share:
            assert eng.pool.cow_count >= 1
            b, before = frozen
            leaf = jax.tree.leaves(eng.runner.cache["units"])[0]
            np.testing.assert_array_equal(before, np.asarray(leaf[:, b]))
            eng.pool.free_slot(1)
        return toks

    mesh = MeshConfig(model=model)
    single = run(None, force_share=False)
    assert run(mesh, force_share=False) == single
    assert run(mesh, force_share=True) == single


def check_int8(model: int):
    """int8 KV through the sharded pool: the quantized pools AND their
    per-(token, head) scale leaves partition over 'model' together, and
    greedy output still matches the single-device int8 engine."""
    prompts = _prompts([6, 19, 11], seed=2)
    base, _ = _serve(prompts, kv_quant=True)
    out, eng = _serve(prompts, mesh=MeshConfig(model=model), kv_quant=True)
    assert out == base, (base, out)
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            eng.runner.cache["units"]):
        assert leaf.sharding.spec[3] == "model", (path, leaf.sharding)


def check_bf16_norm(model: int):
    """Under the serving shardings a per-layer vector (the stacked
    RMS-norm scale) stays replicated, so a bf16 norm computed on the
    mesh is bit-identical to one device. A norm scale sharded on d let
    GSPMD psum the mean of squares per shard: a reordered sum that
    flips bf16 ulps of the residual stream (and, on llama3.2-1b, greedy
    tokens)."""
    import jax.numpy as jnp
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models import layers
    cfg = get_config("llama3.2-1b")
    mesh = make_mesh((1, model), ("data", "model"))
    policy = shd.ShardingPolicy(exact_tp=True)
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (8, 32, cfg.d_model), jnp.bfloat16)
    params = {"units": {"b0": {"norm1": (1.0 + 0.1 * jax.random.normal(
        kw, (cfg.n_units, cfg.d_model))).astype(jnp.bfloat16)}}}
    placed = jax.device_put(params, shd.params_shardings(params, cfg,
                                                         mesh, policy))
    assert placed["units"]["b0"]["norm1"].sharding.spec == \
        jax.sharding.PartitionSpec()

    def norm(params, x):
        w = params["units"]["b0"]["norm1"][0]
        return shd.constrain_tp_exact(layers.rms_norm(x, w, cfg.norm_eps))

    def sharded(params, x):
        with shd.activation_sharding_scope(mesh, policy):
            return norm(params, x)

    one = np.asarray(jax.jit(norm)(params, x), np.float32)
    many = np.asarray(jax.jit(sharded)(placed, x), np.float32)
    assert np.array_equal(one, many), int((one != many).sum())


CHECKS = {
    # model=1 degenerates to the unsharded runner (MeshConfig.n_devices
    # <= 1 -> no mesh); 2 and 4 exercise real partitions of the 4 heads
    "greedy2": lambda: check_greedy(2),
    "greedy4_kvseq": lambda: (check_greedy(4), check_greedy(4,
                                                            kv_seq=True)),
    "spec_prefix4": lambda: check_spec_prefix(4),
    "pool_read2": lambda: check_pool_read(2),
    "cow_int8_2": lambda: (check_cow(2), check_int8(2)),
    "bf16_norm4": lambda: check_bf16_norm(4),
}


def main():
    name = sys.argv[1]
    CHECKS[name]()
    print(f"MESH CHECK PASSED:{name}")


if __name__ == "__main__":
    main()
