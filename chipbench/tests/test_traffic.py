"""The closed-loop generator: the source's strata, the same set of sizes
for every seed, seeded order and ids."""

import collections
import itertools
import json
import os

import numpy as np
import pytest

from chipbench.traffic import ClosedLoop, strata

MIXES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
NAMES = ["azure_conv", "azure_code"]


def mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def test_strata_are_quantile_midpoints():
    d = {"median": 100, "sigma": 1.0, "min": 1, "max": 10 ** 6}
    assert strata(d, 1) == [100]
    # quantiles 0.25 and 0.75 of a standard normal: -/+0.6745
    assert strata(d, 2) == [51, 196]
    assert strata(dict(d, min=60, max=150), 2) == [60, 150]
    five = strata(d, 5)
    assert five[2] == 100 and five == sorted(five)
    assert five[0] * five[4] == pytest.approx(100 * 100, rel=0.01)


@pytest.mark.parametrize("name", NAMES)
def test_every_block_holds_the_same_requests(name):
    m = mix(name)
    want = collections.Counter(ClosedLoop(m, 1000, 1).block)
    assert len(want) == m["strata"]
    for seed in (2 ** 33 + 5, 7):
        loop = ClosedLoop(m, 1000, seed)
        shapes = list(itertools.islice(loop.schedule(), 5 * len(want)))
        for k in range(5):
            assert collections.Counter(
                shapes[k * len(want):(k + 1) * len(want)]) == want
        assert all(n >= 2 for _, n in shapes)


def test_the_medians_are_the_sources():
    for name, (p, n) in zip(NAMES, [(1020, 129), (1500, 13)]):
        m = mix(name)
        assert (m["prompt"]["median"], m["output"]["median"]) == (p, n)
        assert str(p) in m["source"] and str(n) in m["source"]
        block = ClosedLoop(m, 1000, 1).block
        assert sorted(t for t, _ in block)[2] == p
        assert sorted(k for _, k in block)[2] == n


def test_seed_fixes_order_and_ids():
    m = mix("azure_code")
    a, b = ClosedLoop(m, 32256, 7), ClosedLoop(m, 32256, 7)
    c = ClosedLoop(m, 32256, 8)
    first = list(itertools.islice(a.requests(), 3))
    again = list(itertools.islice(b.requests(), 3))
    other = list(itertools.islice(c.requests(), 3))
    for (i, p, n), (j, q, k) in zip(first, again):
        assert i == j and n == k and np.array_equal(p, q)
    assert any(p.shape != q.shape or not np.array_equal(p, q)
               for (_, p, _), (_, q, _) in zip(first, other))
    assert all(0 <= p.min() and p.max() < 32256 for _, p, _ in first)


def test_warmup_has_one_request_per_shape():
    m = mix("azure_conv")
    loop = ClosedLoop(m, 102400, 2 ** 31 + 9)
    shapes = [(w.shape, n) for w, n in loop.warmup()]
    assert shapes == [((m["batch"], t), n) for t, n in sorted(loop.block)]


def test_only_a_closed_loop_of_one_client():
    with pytest.raises(ValueError, match="closed loop"):
        ClosedLoop(dict(mix("azure_conv"), clients=2), 10, 1)


def test_pairing_is_a_permutation():
    with pytest.raises(ValueError, match="permutation"):
        ClosedLoop(dict(mix("azure_conv"), pairing=[0, 0, 1, 2, 3]), 10, 1)
