"""The traffic generator: deterministic per seed, the same work for every
seed, the stated length moments, and mixes found by file name."""
import json
import shutil

import numpy as np
import pytest

from chipbench import spec, traffic

DATA = spec.HERE / "tests" / "data"
MIXES = ["sharegpt", "codefuse.steady"]


def _mix(name):
    """A mix of the benchmark, or of the tests' own data."""
    root = DATA if (DATA / "traffic" / f"{name}.json").exists() else spec.HERE
    return spec.traffic(name, root)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    a = traffic.schedule(mix, 2 ** 31 + 17, 60.0, 1000)
    b = traffic.schedule(mix, 2 ** 31 + 17, 60.0, 1000)
    assert [(p.due, p.gen_len) for p in a] == [(p.due, p.gen_len) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("fixed_order", [True, False])
def test_every_seed_gets_the_same_work(name, fixed_order):
    """The same lengths and gaps for every seed: in the mix's own order
    where it states ``order_seed``, else in an order the seed draws."""
    mix = dict(_mix(name))
    if not fixed_order:
        mix.pop("order_seed", None)
    a = traffic.schedule(mix, 1, 60.0, 1000)
    b = traffic.schedule(mix, 2, 60.0, 1000)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.gen_len for p in a) == sorted(p.gen_len for p in b)
    same = [(p.due, len(p.prompt), p.gen_len) for p in a] == \
        [(p.due, len(p.prompt), p.gen_len) for p in b]
    assert same == fixed_order
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert a[0].due == 0.0 and b[0].due == 0.0
    assert a[-1].due == pytest.approx(b[-1].due)
    assert all(2 <= t < 1000 for p in a for t in p.prompt)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("part", ["prompt", "gen"])
def test_length_moments(name, part):
    """A long draw's mean and bounds are the clipped lognormal's, whose
    mean is worked out here by numerical integration of its density."""
    mix = _mix(name)
    d = mix[part]
    plan = traffic.schedule(mix, 5, 2000.0 / mix["arrivals"]["rate_rps"], 10)
    x = np.array([len(p.prompt) if part == "prompt" else p.gen_len
                  for p in plan])
    assert x.min() >= d["min"] and x.max() <= d["max"]
    # E[clip(round(X), lo, hi)] for X ~ lognormal(mu, sigma)
    z = np.linspace(-9, 9, 400001)
    pdf = np.exp(-z * z / 2) / np.sqrt(2 * np.pi)
    v = np.clip(np.round(np.exp(d["mu"] + d["sigma"] * z)), d["min"],
                d["max"])
    want = float(np.sum(v * pdf) * (z[1] - z[0]))
    assert x.mean() == pytest.approx(want, rel=0.02)


def test_arrival_rate():
    mix = spec.traffic("codefuse.steady")
    plan = traffic.schedule(mix, 3, 100.0, 10)
    assert len(plan) == round(mix["arrivals"]["rate_rps"] * 100)
    gaps = np.diff([p.due for p in plan])
    assert gaps.min() >= 0
    # exponential gaps: the standard deviation is about the mean
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.15)


def test_new_mix_is_found_by_name(tmp_path):
    """A cell naming a new traffic file runs that file, with no edit to
    any file that is already there."""
    root = tmp_path / "bench"
    shutil.copytree(spec.HERE / "configs", root / "configs")
    shutil.copytree(spec.HERE / "metrics", root / "metrics")
    (root / "traffic").mkdir()
    mix = dict(spec.traffic("codefuse.steady"))
    mix["arrivals"] = {"process": "gamma", "shape": 0.5, "rate_rps": 3.0}
    (root / "traffic" / "bursty.new.json").write_text(json.dumps(mix))
    bench = {"workloads": [{"name": "m.bursty", "config":
                            "mistral-7b-v0.3-l16", "traffic": "bursty.new",
                            "chips": 1}],
             "end_to_end": [{"name": "out_tok_s"}],
             "per_layer": [{"name": "idle_share.lat"}]}
    cell = spec.cell("m.bursty", bench, root=root)
    assert cell.traffic["arrivals"]["process"] == "gamma"
    plan = traffic.schedule(cell.traffic, 1, 100.0, 50)
    gaps = np.diff([p.due for p in plan])
    assert gaps.std() / gaps.mean() > 1.2  # burstier than Poisson
    assert callable(cell.readers["idle_share.lat"])
