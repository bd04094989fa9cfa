"""Metric arithmetic: a rate over the whole window, percentiles over every
query, and a stall inside the window showing in p99_ms."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, traffic
from bench.metrics import _lib

CFG = {"k": 2, "index": {"nprobe": 4},
       "serving": {"num_slots": 4, "steps_per_sync": 2}}


def _run(calls, window_s, latencies=None, trace=None):
    return harness.Run("cell", {"dim": 128}, {}, "TPU v5 lite", 1.0, {},
                       calls, window_s, latencies, trace)


def _call(t0, t1, n, chunk_ms=None, steps=10):
    return harness.Call(t0, t1, n, n, 0, steps, steps * n, 100 * n, chunk_ms)


def test_rate_is_all_work_over_all_window_time():
    # a slow call counts with all its time, not as one call among others
    calls = [_call(0.0, 1.0, 100), _call(1.0, 1.5, 100), _call(1.5, 5.0, 100)]
    assert harness.reader("qps")(_run(calls, 5.0)) == pytest.approx(60.0)


def test_percentiles_over_every_query_not_per_call():
    lat = np.concatenate([np.full(990, 10.0), np.full(10, 1000.0)])
    run = _run([_call(0, 1, 1000)], 1.0, latencies=lat)
    assert harness.reader("p50_ms")(run) == pytest.approx(10.0)
    assert harness.reader("p99_ms")(run) == pytest.approx(1000.0)
    assert _lib.percentile([], 99) is None


def test_host_share_and_steps_per_query():
    calls = [_call(0.0, 1.0, 10, chunk_ms=750.0),
             _call(1.0, 2.0, 30, chunk_ms=750.0)]
    run = _run(calls, 2.0)
    assert harness.reader("host_share.batch")(run) == pytest.approx(25.0)
    assert harness.reader("steps_per_query.batch")(run) == pytest.approx(10)
    untraced = _run([_call(0.0, 1.0, 10)], 1.0)
    assert harness.reader("host_share.batch")(untraced) is None


def test_exhausted_share_over_every_completed_query():
    calls = [_call(0.0, 1.0, 100), _call(1.0, 2.0, 300)]
    run = _run(calls, 2.0)
    assert harness.reader("exhausted_share.batch")(run) is None
    calls[0].exhausted, calls[1].exhausted = 0, 2
    assert harness.reader("exhausted_share.batch")(run) == \
        pytest.approx(0.5)


def test_serve_call_counts_queries_that_probe_every_list():
    from repro.obs import metrics as obs_metrics
    reg = obs_metrics.serve_metrics(obs_metrics.MetricsRegistry())
    steps_h = reg.histogram("darth_service_steps")
    steps_h.observe(9.0)            # an earlier call's query: not counted

    class Server:
        def serve(self, q, targets, max_engine_steps):
            for v in (3.0, 4.0, 5.0):   # nprobe 4: two probed every list
                steps_h.observe(v)
            reg.histogram("darth_chunk_latency_ms").observe(2.5)
            return _Server().serve(q, targets, max_engine_steps)
    q = np.zeros((3, 2), np.float32)
    _, call = harness._serve(Server(), CFG, q, np.full(3, 0.9), reg,
                             lambda name: __import__(
                                 "contextlib").nullcontext(), 0.0)
    assert call.exhausted == 2 and call.chunk_ms_sum == pytest.approx(2.5)


def test_device_readers_need_a_trace():
    run = _run([_call(0, 1, 1000)], 1.0)
    for name in ("idle_share.batch", "probe_roofline.batch"):
        assert harness.reader(name)(run) is None
    traced = _run([_call(0, 1, 1000)], 1.0,
                  trace={"busy_s": 0.5, "window_s": 2.0})
    assert harness.reader("idle_share.batch")(traced) == pytest.approx(75.0)
    share = harness.reader("probe_roofline.batch")(traced)
    assert 0 < share < 100


class _Server:
    """Answers instantly, except for one call that stalls."""

    def __init__(self, stall_call=None, stall_s=0.3):
        self.calls, self.stall_call, self.stall_s = 0, stall_call, stall_s

    def serve(self, q, targets, max_engine_steps):
        if self.calls == self.stall_call:
            time.sleep(self.stall_s)
        self.calls += 1
        n = q.shape[0]
        results = [(np.zeros(2), np.arange(2)) for _ in range(n)]
        stats = SimpleNamespace(completed=n, truncated=0, engine_steps=1,
                                slot_steps=n,
                                ndis_harvested=n)
        return results, stats


def _p99(stall_call):
    mix = {"loop": "open", "rate_qps": 200.0, "targets": [0.9]}
    plan = traffic.plan(mix, 1, 1.0, 64)
    pool = np.zeros((64, 3), np.float32)
    calls, answers, window_s, lat = harness.open_window(
        _Server(stall_call), CFG, plan, pool, None,
        lambda name: __import__("contextlib").nullcontext())
    assert sum(a.answered.sum() for a in answers) == plan.due_s.shape[0]
    return harness.reader("p99_ms")(_run(calls, window_s, lat))


def test_a_stall_in_the_window_moves_p99():
    calm, stalled = _p99(None), _p99(20)
    # every query due during the stall waits for it
    assert stalled > calm + 100.0
