"""Sharded serving (ServeConfig.mesh): greedy token-identity to the
single-device paged engine on a forced 4-device host mesh — plain
decode, speculation + prefix sharing, copy-on-write, int8 KV, the
seq-sharded LSE-combine decode path, and the whole-pool width-1 read —
plus metrics shard-consistency.

Each case runs tests/mesh_worker.py in a subprocess so the forced device
count doesn't leak into other tests (same pattern as test_dist.py); the
check groups inside the worker parametrize the mesh size (model=1 is the
no-mesh degenerate case, model=2/4 real partitions of the 4 KV heads).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("check", ["greedy2", "greedy4_kvseq",
                                   "spec_prefix4", "cow_int8_2",
                                   "bf16_norm4", "pool_read2"])
def test_mesh_serving_4dev(check):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "mesh_worker.py"),
         check],
        capture_output=True, text=True, timeout=560, cwd=REPO, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert f"MESH CHECK PASSED:{check}" in r.stdout


def test_mesh_requires_paged():
    """MeshConfig on the legacy slot engine must be rejected loudly, and
    model=1 must be accepted as the no-mesh degenerate case (no devices
    required)."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import MeshConfig, ServeConfig
    from repro.models import Model
    from repro.serve.engine import Engine
    from repro.serve.scheduler import Request

    cfg = get_config("nectar-relu-llama-1.7m")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, params, ServeConfig(paged=False,
                                        mesh=MeshConfig(model=2)))
    eng = Engine(cfg, params,
                 ServeConfig(paged=True, max_batch=2, max_seq=64,
                             block_size=8, mesh=MeshConfig(model=1)))
    assert eng.mesh is None
    assert eng.metrics.summary()["mesh"] == {}
    done = eng.run([Request(rid=0,
                            prompt=np.arange(5, dtype=np.int32),
                            max_new=4)], max_steps=200)
    assert len(done[0].tokens_out) == 4
