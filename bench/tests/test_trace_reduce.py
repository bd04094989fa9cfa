import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event

DATA = Path(__file__).parent / "data"


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_self_time_subtracts_nested_ops():
    ops = [Event("while", 0, 100), Event("a", 10, 30),
           Event("cond", 40, 90), Event("b", 50, 60)]
    t = tr.self_times(ops)
    assert t == {"while": 30.0, "a": 20.0, "cond": 40.0, "b": 10.0}


def test_busy_idle_and_gap_attribution():
    ops = [Event("while", 10, 60), Event("a", 20, 30), Event("x", 80, 90)]
    spans = [Event("window", 0, 100), Event("serve_call", 0, 70),
             Event("collect", 70, 100)]
    r = tr.reduce([ops], spans)
    assert r["busy_s"] == pytest.approx(60e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["device_ops"][0] == ["while", pytest.approx(40e-9)]
    # longest gap first, named by the harness span open at its middle
    assert [g[0] for g in r["idle_gaps"]] == ["collect", "serve_call",
                                              "collect"]
    assert r["idle_gaps"][0][1] == pytest.approx(20e-9)


def test_ops_outside_the_window_do_not_count():
    ops = [Event("before", 0, 50), Event("in", 60, 80)]
    r = tr.reduce([ops], [Event("window", 40, 100)])
    assert r["busy_s"] == pytest.approx(30e-9)


def test_no_window_or_no_device_gives_nothing():
    assert tr.reduce([[Event("a", 0, 1)]], []) is None
    assert tr.reduce([], [Event("window", 0, 1)]) is None
    assert tr.reduce([[]], [Event("window", 0, 1)]) is None


def test_recorded_v5e_trace():
    """The first 108 ms of a traced window on a TPU v5e: three chunk
    programs, the host loop's gaps between them inside the serve call."""
    d = json.loads((DATA / "trace_v5e.json").read_text())
    ops = tr.device_ops(d["ops"], d["modules"])
    spans = [Event(n, t, t + dur) for n, t, dur in d["spans"]]
    r = tr.reduce([ops], spans)
    assert r["window_s"] == pytest.approx(0.066801714)
    assert r["busy_s"] == pytest.approx(0.046769886)
    assert r["device_ops"][0] == ["jit_run_chunk/%fusion.97",
                                 pytest.approx(0.008646381)]
    assert all(name == "serve_call" for name, _ in r["idle_gaps"])
    assert r["idle_gaps"][0][1] == pytest.approx(0.006342205)
    self_total = sum(tr.self_times(
        [Event(e.name, max(e.start_ns, spans[0].start_ns), e.end_ns)
         for e in ops]).values()) * 1e-9
    # self times partition the busy time: nothing counted twice
    assert self_total == pytest.approx(r["busy_s"], rel=1e-6)
