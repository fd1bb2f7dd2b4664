"""Each mix is seeded and deterministic at its rate, and every seed gets
the same work."""

import glob
import json
import os

import numpy as np
import pytest

import chipbench_paths  # noqa: F401  (puts the harness on sys.path)

from lib.traffic import make_traffic

MIXES = sorted(glob.glob(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "mixes", "*.json")))
BIG = 2 ** 33 + 5


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_same_seed_same_requests(path):
    mix = _load(path)
    a, b = make_traffic(mix, BIG, 20.0), make_traffic(mix, BIG, 20.0)
    assert np.array_equal(a.ids, b.ids)
    assert np.array_equal(a.prefix, b.prefix)
    if a.due_s is not None:
        assert np.array_equal(a.due_s, b.due_s)
    c = make_traffic(mix, BIG + 1, 20.0)
    assert not np.array_equal(a.ids, c.ids)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_seeds_share_the_work(path):
    mix = _load(path)
    a, b = make_traffic(mix, 3, 30.0), make_traffic(mix, BIG, 30.0)
    assert len(a.prefix) == len(b.prefix) == mix.get("prefix_requests", 0)
    assert len(a.objects) == len(b.objects)
    # the access pattern is the same up to a relabelling of objects
    ka = np.unique(a.ids, return_inverse=True)[1]
    kb = np.unique(b.ids, return_inverse=True)[1]
    assert sorted(np.bincount(ka)) == sorted(np.bincount(kb))
    if mix["loop"] == "open":
        rate = mix["arrivals"]["rate_per_s"]
        for t in (a, b):
            assert len(t.due_s) == round(rate * 30.0)
            assert np.all(np.diff(t.due_s) >= 0)
            assert 0 <= t.due_s[0] and t.due_s[-1] < 30.0
        assert np.array_equal(a.due_s, b.due_s)
        gaps = np.diff(a.due_s)
        # Poisson: exponential spacings, mean 1/rate
        assert np.mean(gaps) == pytest.approx(1.0 / rate, rel=0.15)
        assert np.std(gaps) == pytest.approx(1.0 / rate, rel=0.25)


def test_scan_never_repeats_within_a_pass():
    mix = _load([p for p in MIXES if "scan" in p][0])
    t = make_traffic(mix, BIG, 1.0)
    corpus = mix["corpus"]
    assert len(set(t.ids[:corpus].tolist())) == corpus
    assert np.array_equal(t.ids[:corpus], t.ids[corpus:2 * corpus])
