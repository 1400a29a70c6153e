"""The copied generators and the fault profile: the same seed gives the same
traffic, another seed other traffic, with the same shapes."""

import random

import numpy as np

from benchmark import traffic_gen

BIG = 2**31 + 99991


def test_synctest_batches_deterministic():
    a = traffic_gen.synctest_batches(BIG, 2, 60, 64)
    assert a.shape == (64, 60, 2, 1) and a.dtype == np.uint8
    assert np.array_equal(a, traffic_gen.synctest_batches(BIG, 2, 60, 64))
    assert not np.array_equal(a, traffic_gen.synctest_batches(BIG + 1, 2, 60, 64))
    assert a.max() < 16


def test_held_scripts_hold_6_to_18_frames():
    a = traffic_gen.held_scripts(BIG, 5, 4, 2000)
    assert np.array_equal(a, traffic_gen.held_scripts(BIG, 5, 4, 2000))
    assert not np.array_equal(a, traffic_gen.held_scripts(BIG + 1, 5, 4, 2000))
    for row in a.reshape(-1, 2000):
        edges = np.flatnonzero(np.diff(row)) + 1
        runs = np.diff(edges)
        assert runs.min() >= 6 and runs.max() <= 18
        assert set(row.tolist()) <= set(traffic_gen.HOLD_CYCLE)


def test_wan_link_seeded_latency_and_loss():
    link = traffic_gen.WanLink(80, 20, 0.03)

    def draw(seed):
        rng = random.Random(seed)
        return [link.link("a", "b", 0, rng) for _ in range(20000)]

    a = draw(BIG)
    assert a == draw(BIG) and a != draw(BIG + 1)
    delays = [d[0] for d in a if d]
    assert min(delays) >= 60 and max(delays) <= 100
    assert abs(np.mean(delays) - 80) < 1
    assert abs(sum(not d for d in a) / len(a) - 0.03) < 0.005
