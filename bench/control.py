"""Readings for a cell's correctness limits, on the chip: for each seed,
serve a short window at the cell's own load, let it finish, and score
the sample of served tokens with the float32 reference (the program's
readings) and with the reference's float8 control at the same positions
(the control's readings). With ``--fault-seeds`` the program is also run
with a planted fault, a sampler that serves the second-best token with
its own log-probability, and read the same way. All seeds run in one
process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 20 \
        --control 3 --fault-seeds 4,5,6

The limits in ``bench/cells/<workload>.json`` lie above the largest
program reading and below the smallest control or fault reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import arrivals, harness  # noqa: E402


def second_best(self, logits, *_, **__):
    """Sampler fault: the second-best token, with its own log-probability."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    z = jnp.asarray(logits, jnp.float32)
    tok = jax.lax.top_k(z, 2)[1][:, 1]
    lp = jnp.take_along_axis(jax.nn.log_softmax(z, -1), tok[:, None],
                             1)[:, 0]
    return np.asarray(tok), np.asarray(lp)


def read(cell, seed: int, seconds: float, counter, control: bool) -> dict:
    params = harness.make_weights(cell, seed)
    eng = harness.make_engine(cell, params, traced=False)
    harness.warm_up(eng, seed)
    offered = arrivals.generate(cell.mix, cell.settings.get("rate_per_s", 0),
                                seconds, seed, cell.conf["vocab_size"])
    win = harness.serve_window(eng, offered, seconds, counter)
    harness.free(eng)
    del eng
    sample = harness.sample_for_check(win, seed)
    out = {"program": harness.check(cell, params, sample)}
    if control:
        out["control"] = harness.check(cell, params, sample, control=True)
    del params, sample, win
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=3,
                    help="read the control on the first N seeds only")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.ensure_src()
    import jax
    harness.device_check(cell.chips)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = harness.CompileCounter()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = read(cell, seed, args.seconds, counter, i < args.control)
        print(json.dumps({"seed": seed, **out}), flush=True)
    if args.fault_seeds:
        from repro.serve import sampling
        sampling.Sampler.__call__ = second_best
        for seed in (int(s) for s in args.fault_seeds.split(",")):
            out = read(cell, seed, args.seconds, counter, False)
            print(json.dumps({"seed": seed, "fault": out["program"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
