"""The traffic generator: deterministic from the seed, the same work at the
same times for every seed, and the mix's parameters held."""
import json
import os

import numpy as np
import pytest

from bench import generator, run

MIX = {"rate_per_s": 0.8,
       "prompt": {"dist": "lognormal", "median": 2048, "sigma": 0.7,
                  "min": 256, "max": 8176},
       "output": {"dist": "fixed", "value": 16},
       "engine": {"chunk": 512}}
BIG = 2 ** 31 + 12345


def _sig(reqs):
    return [(r.due_s, len(r.prompt), r.max_new, r.prompt[:4].tolist())
            for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, BIG, 2 ** 40 + 3])
def test_requests_are_deterministic(seed):
    a = generator.requests(MIX, seed, 30, 152064)
    b = generator.requests(MIX, seed, 30, 152064)
    assert _sig(a) == _sig(b)


def test_every_seed_gets_the_same_schedule():
    """Only the token ids depend on the seed: lengths and due times come
    in one fixed order."""
    a = generator.requests(MIX, 1, 30, 1000)
    b = generator.requests(MIX, BIG, 30, 1000)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert _sig(a) != _sig(b)
    lens = [len(r.prompt) for r in a]
    assert lens != sorted(lens)                 # not in ascending order


def test_poisson_rate_and_lengths_hold():
    reqs = generator.requests(MIX, 3, 50, 1000)
    assert len(reqs) == 40                      # rate x window
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[-1] == pytest.approx(50)
    gaps = np.diff([0.0] + due)
    assert gaps.mean() == pytest.approx(1 / 0.8, rel=1e-9)
    cv = gaps.std() / gaps.mean()               # exponential: about 1
    assert 0.8 < cv < 1.1
    lens = np.array([len(r.prompt) for r in reqs])
    assert lens.min() >= 256 and lens.max() <= 8176
    assert np.median(lens) == pytest.approx(2048, rel=0.05)
    assert all(r.max_new == 16 for r in reqs)


@pytest.mark.parametrize("dist,lo,hi,mean", [
    ({"dist": "uniform", "min": 128, "max": 512}, 128, 512, 320),
    ({"dist": "fixed", "value": 7}, 7, 7, 7),
])
def test_length_distributions(dist, lo, hi, mean):
    q = generator.quantiles(dist, 64)
    assert q.min() >= lo and q.max() <= hi
    assert q.mean() == pytest.approx(mean, abs=4)


def test_warm_up_covers_the_step_buckets():
    w = generator.warm_up(MIX, 100)
    assert all(len(r.prompt) == 513 and r.max_new == 3 for r in w)
    one = dict(MIX, output={"dist": "fixed", "value": 1})
    assert [r.max_new for r in generator.warm_up(one, 100)] == [1, 1]


def test_a_mix_may_name_its_own_generator(tmp_path, monkeypatch):
    """A later mix brings its generator as a file of its own; a mix that
    names none gets the stock one."""
    assert generator.for_mix(MIX) is generator
    (tmp_path / "burst.py").write_text(
        "from bench import generator\n"
        "def requests(mix, seed, seconds, vocab):\n"
        "    return generator.requests(mix, seed, seconds, vocab)[:2]\n"
        "warm_up = generator.warm_up\n")
    monkeypatch.setattr(generator, "TRAFFIC", str(tmp_path))
    mod = generator.for_mix(dict(MIX, generator="burst"))
    assert len(mod.requests(MIX, 1, 30, 100)) == 2


def test_every_committed_mix_reads():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        with open(os.path.join(run.HERE, "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        gen = generator.for_mix(mix)
        reqs = gen.requests(mix, 5, 51, 1000)
        eng = mix["engine"]
        assert all(len(r.prompt) + r.max_new <= eng["max_seq"]
                   for r in reqs)
