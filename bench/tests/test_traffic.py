"""The generator: fixed schedules, bounded lengths, seeded prompts."""

import json
import os

import numpy as np
import pytest

from benchlib import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "..",
                                                       "traffic")))


def _mix(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_schedule_is_fixed_and_bounded(name):
    mix = _mix(name)
    a, b = traffic.schedule(mix, 40), traffic.schedule(mix, 40)
    assert a == b and a
    assert all(0 <= r["arrival"] < 40 for r in a)
    lo = min(c.get("min", 0) for c in mix["prompt"].get("mixture",
                                                         [mix["prompt"]]))
    assert all(r["prompt_len"] >= lo for r in a)
    # a longer window keeps the shorter one's requests
    assert traffic.schedule(mix, 80)[:len(a)] == a


def test_poisson_rate_and_mixture_share():
    mix = {"schedule_seed": 3,
           "arrival": {"process": "poisson", "rate_per_s": 2.0},
           "prompt": {"mixture": [
               {"weight": 0.8, "dist": "loguniform", "min": 10, "max": 20},
               {"weight": 0.2, "dist": "fixed", "value": 1000}]},
           "output": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                      "min": 16, "max": 256}}
    reqs = traffic.schedule(mix, 2000)
    assert len(reqs) == pytest.approx(4000, rel=0.05)
    long = sum(r["prompt_len"] == 1000 for r in reqs) / len(reqs)
    assert long == pytest.approx(0.2, abs=0.02)
    assert all(16 <= r["output_len"] <= 256 for r in reqs)
    assert np.median([r["output_len"] for r in reqs]) == \
        pytest.approx(64, rel=0.1)


def test_prompt_tokens_from_the_seed():
    mix = {"schedule_seed": 4,
           "arrival": {"process": "poisson", "rate_per_s": 1.0},
           "prompt": {"dist": "fixed", "value": 50},
           "output": {"dist": "fixed", "value": 4}}
    reqs = traffic.schedule(mix, 50)
    t1 = traffic.prompt_tokens(reqs, 100, 2**40 + 3)
    t2 = traffic.prompt_tokens(reqs, 100, 2**40 + 3)
    t3 = traffic.prompt_tokens(reqs, 100, 2**40 + 4)
    assert all((t1[r] == t2[r]).all() for r in t1)
    assert any((t1[r] != t3[r]).any() for r in t1)
    assert all(len(t1[r["rid"]]) == 50 for r in reqs)


def test_batch_arrivals_keep_the_rate_and_cluster():
    mix = {"schedule_seed": 5,
           "arrival": {"process": "poisson", "rate_per_s": 2.0,
                       "batch": {"size": 8, "spread_s": 0.2}},
           "prompt": {"dist": "fixed", "value": 50},
           "output": {"dist": "fixed", "value": 4}}
    reqs = traffic.schedule(mix, 4000)
    assert len(reqs) == pytest.approx(8000, rel=0.05)
    t = [r["arrival"] for r in reqs]
    assert t == sorted(t)
    # most requests have seven others within the spread of their batch
    near = sum(sum(abs(u - x) <= 0.2 for u in t[max(0, i - 8):i + 8]) >= 8
               for i, x in enumerate(t))
    assert near / len(t) > 0.9
    # a longer window keeps the shorter one's requests
    assert traffic.schedule(mix, 8000)[:len(reqs)] == reqs
