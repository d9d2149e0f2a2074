"""The benchmark's blockwise float32 reference against the program's own
teacher-forced reference (``repro.serve.reference``) at smoke size, on
weights the benchmark made."""

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests import smoke


@pytest.mark.parametrize("conf_extra", [
    {}, {"qk_norm": False, "qkv_bias": True}])
def test_equals_teacher_forced_gaps(conf_extra):
    from repro.serve.reference import teacher_forced_gaps
    conf = dict(smoke.CONF, **conf_extra)
    fam = harness.family(conf)
    params = jax.jit(lambda k: fam.init_weights(conf, k))(
        harness.seed_key(2 ** 33 + 11))
    cfg = fam.model_config(conf)
    ref = harness.reference(conf)
    rng = np.random.default_rng(0)
    for P, n in ((5, 7), (40, 30), (600, 4)):
        prompt = rng.integers(0, 256, P, dtype=np.int32)
        served = rng.integers(0, 256, n, dtype=np.int32)
        mine, lp = ref.served_scores(conf, params, prompt, served)
        (theirs,), _ = teacher_forced_gaps(cfg, params, [prompt], [served])
        np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-4)
        assert mine.max() > 0 and np.all(lp < 0)
