"""The traffic generator: deterministic from its seed, inside its clip
ranges, and the same trace for every seed, with other token ids."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import arrivals

TRAFFIC = Path(__file__).resolve().parent.parent / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_from_seed(name):
    a = arrivals.generate(_mix(name), 1.3, 20.0, BIG_SEED, 151936)
    b = arrivals.generate(_mix(name), 1.3, 20.0, BIG_SEED, 151936)
    c = arrivals.generate(_mix(name), 1.3, 20.0, BIG_SEED + 1, 151936)
    assert [(o.due, o.max_new) for o in a] == [(o.due, o.max_new) for o in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_clip_ranges_and_vocab(name):
    mix = _mix(name)
    reqs = arrivals.generate(mix, 1.3, 20.0, 7, 1000)
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    assert all(r.prompt.min() >= 0 and r.prompt.max() < 1000 for r in reqs)


@pytest.mark.parametrize("name", MIXES)
def test_same_work_every_seed(name):
    mix = _mix(name)
    a = arrivals.generate(mix, 1.3, 20.0, 1, 50)
    b = arrivals.generate(mix, 1.3, 20.0, BIG_SEED, 50)
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_are_the_quantiles_in_a_mixed_order(name):
    mix = _mix(name)
    reqs = arrivals.generate(mix, 1.3, 20.0, 5, 50)
    u = arrivals._quantiles(len(reqs))
    lens = [len(r.prompt) for r in reqs]
    assert sorted(lens) == sorted(arrivals._ppf(mix["prompt_len"], u))
    assert lens != sorted(lens)


def test_poisson_due_times_inside_window():
    mix = _mix("chat")
    reqs = arrivals.generate(mix, 1.3, 40.0, 3, 50)
    due = [r.due for r in reqs]
    assert len(reqs) == 52
    assert due == sorted(due) and 0 < due[0] and due[-1] <= 40.0 + 1e-9
    gaps = sorted(np.diff([0.0] + due))
    gaps2 = sorted(np.diff([0.0] + [r.due for r in arrivals.generate(
        mix, 1.3, 40.0, 4, 50)]))
    np.testing.assert_allclose(gaps, gaps2)


def test_unknown_arrival_process_is_refused():
    mix = dict(_mix("chat"), arrival={"process": "bursty"})
    with pytest.raises(ValueError):
        arrivals.generate(mix, 1.0, 10.0, 3, 50)


def test_lognormal_median():
    mix = {"dist": "lognormal", "median": 384, "sigma": 1.0, "min": 1,
           "max": 10 ** 9}
    x = arrivals._ppf(mix, arrivals._quantiles(1001))
    assert x[500] == 384
