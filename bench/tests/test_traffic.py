import numpy as np
import pytest

from bench import traffic

OPEN = {"loop": "open", "rate_qps": 400.0, "targets": [0.8, 0.9, 0.95]}


def test_every_seed_gets_the_same_gaps_in_its_own_order():
    a = traffic.arrivals(OPEN, 1, 10.0)
    b = traffic.arrivals(OPEN, 2 ** 32 + 5, 10.0)
    ga, gb = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    assert a.shape == b.shape == (4000,)
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert np.all(np.diff(a) >= 0)
    # the mean rate is the mix's
    assert a[-1] == pytest.approx(10.0, rel=0.01)


def test_same_seed_same_plan_other_seed_same_work():
    p1 = traffic.plan(OPEN, 7, 5.0, 4096)
    p2 = traffic.plan(OPEN, 7, 5.0, 4096)
    p3 = traffic.plan(OPEN, 8, 5.0, 4096)
    assert np.array_equal(p1.due_s, p2.due_s)
    assert np.array_equal(p1.query_ids, p3.query_ids)
    assert np.array_equal(p1.targets, p3.targets)
    assert not np.array_equal(p1.due_s, p3.due_s)


@pytest.mark.parametrize("n", [2048, 2047])
def test_targets_are_balanced(n):
    mix = {"loop": "closed", "call_queries": n, "targets": [0.8, 0.9, 0.95]}
    _, t = traffic.closed_call(mix, 3, 0, 4096)
    counts = [int(np.sum(np.isclose(t, v))) for v in (0.8, 0.9, 0.95)]
    assert sum(counts) == n and max(counts) - min(counts) <= 1


def test_closed_calls_are_the_same_work_in_another_order():
    mix = {"loop": "closed", "call_queries": 300, "targets": [0.8, 0.9]}
    a_ids, a_t = traffic.closed_call(mix, 1, 3, 1000)
    b_ids, b_t = traffic.closed_call(mix, 2, 3, 1000)
    assert not np.array_equal(a_ids, b_ids)
    assert sorted(zip(a_ids, a_t)) == sorted(zip(b_ids, b_t))


def test_closed_calls_cycle_the_pool():
    mix = {"loop": "closed", "call_queries": 300, "targets": [0.9]}
    seen = np.concatenate([traffic.closed_call(mix, 5, c, 1000)[0]
                           for c in range(10)])
    assert set(seen.tolist()) == set(range(1000))

