"""The program's spans, scopes and counters read from a trace: gaps named
by the innermost program span, device time split by scope, the counters
summed per window, the op paths read from the file's wire format, and the
five readers built on them."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, program_trace as pt
from bench import trace_reduce as tr
from bench.program_trace import Op, Span

DATA = Path(__file__).parent / "data"


def test_scope_of_takes_the_innermost_darth_scope():
    assert pt.scope_of("jit(run_chunk)/while/body/closed_call/darth.probe/"
                       "jit(probe_step)/darth.merge/top_k:") == "darth.merge"
    assert pt.scope_of("jit(run_chunk)/while/body/closed_call/cond/"
                       "branch_1_fun/darth.predict/ge:") == "darth.predict"
    assert pt.scope_of("jit(splice)/jit(_where)/select_n:") == "none"
    assert pt.scope_of("") == "none"


def test_program_spans_win_the_naming_of_gaps():
    ops = [Op("a", 10, 20, "darth.probe"), Op("b", 40, 50, "darth.predict")]
    spans = [Span("window", 0, 100), Span("serve_call", 5, 90),
             Span("darth.serve", 6, 89),
             Span("darth.serve.harvest", 20, 30),
             Span("darth.serve.refill", 30, 38),
             Span("collect", 90, 100)]
    r = pt.reduce([ops], spans)
    # the longest gap, 50-100, is named by the span open at its middle
    assert r["idle_gaps"][0] == ["darth.serve", pytest.approx(50e-9)]
    # each part of a gap goes to the span innermost there
    assert r["idle_by_span"] == pytest.approx({
        "darth.serve.harvest": 10e-9, "darth.serve.refill": 8e-9,
        "darth.serve": (4 + 2 + 39) * 1e-9, "serve_call": 2e-9,
        "none": 5e-9, "collect": 10e-9})
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert r["spans_s"] == pytest.approx({
        "darth.serve": 83e-9, "darth.serve.harvest": 10e-9,
        "darth.serve.refill": 8e-9})


def test_device_by_scope_partitions_busy_time():
    # the loop op holds no scope; its body ops do; one op runs outside
    # any program (scope "none")
    ops = [Op("while", 0, 100, "none"), Op("gather", 10, 30, "darth.probe"),
           Op("topk", 30, 35, "darth.merge"),
           Op("trees", 40, 90, "darth.predict"),
           Op("splice", 120, 130, "none")]
    r = pt.reduce([ops], [Span("window", 0, 200)])
    assert r["device_by_scope"] == pytest.approx({
        "darth.predict": 50e-9, "darth.probe": 20e-9, "darth.merge": 5e-9,
        "none": 35e-9})
    assert sum(r["device_by_scope"].values()) == pytest.approx(r["busy_s"])


def test_counters_sum_over_the_calls_that_end_in_the_window():
    stats = {"predictor_calls": 30, "predictor_batches": 10, "num_slots": 8}
    spans = [Span("window", 0, 100), Span("darth.serve", 0, 40, stats),
             Span("darth.serve", 40, 90, dict(stats, predictor_calls=50)),
             Span("darth.serve", 95, 120, stats)]
    r = pt.reduce([[Op("a", 1, 2, "none")]], spans)
    assert r["counters"] == {"predictor_calls": 80, "predictor_batches": 20,
                             "num_slots": 16, "slot_evaluations": 160}


def test_scoped_ops_find_the_program_each_op_ran_in():
    paths = {(1, "%fusion = a"): "jit(run_chunk)/darth.probe/dot:",
             (2, "%fusion = a"): "jit(init_chunk)/dot:"}
    ops = [("%fusion = a", 10, 5), ("%fusion = a", 110, 5)]
    modules = [("jit_run_chunk(1)", 0, 50), ("jit_init_chunk(2)", 100, 50)]
    got = pt.scoped_ops(ops, modules, paths)
    assert [(o.name, o.scope) for o in got] == [("%fusion", "darth.probe"),
                                                ("%fusion", "none")]


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def test_op_paths_read_the_event_metadata(tmp_path):
    """An XSpace with a host plane and a TPU plane whose event metadata
    carries tf_op as a string and as a reference to an interned name."""
    def stat_meta(sid, name):
        return _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                + _field(2, name)))

    def event_meta(eid, name, *stats):
        body = _field(1, eid) + _field(2, name) + b"".join(
            _field(5, s) for s in stats)
        return _field(4, _field(1, eid) + _field(2, body))
    tpu = (_field(2, "/device:TPU:0") + _field(3, b"\x0a\x00")
           + event_meta(1, "%fusion.1 = f32[2] fusion()",
                        _field(1, 7) + _field(5, "jit(f)/darth.probe/dot:"),
                        _field(1, 8) + _field(3, 42))
           + event_meta(2, "%copy = f32[2] copy()",
                        _field(1, 7) + _field(7, 9))
           + stat_meta(7, "tf_op") + stat_meta(8, "program_id")
           + stat_meta(9, "jit(f)/darth.predict/copy:"))
    host = _field(2, "/host:CPU") + event_meta(1, "python")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, host) + _field(1, tpu))
    assert pt.op_paths(str(path)) == {"/device:TPU:0": {
        (42, "%fusion.1 = f32[2] fusion()"): "jit(f)/darth.probe/dot:",
        (None, "%copy = f32[2] copy()"): "jit(f)/darth.predict/copy:"}}


def _fixture(name):
    return json.loads((DATA / name).read_text())


def test_recorded_v5e_trace_with_program_spans():
    """~100 ms of a traced window on a TPU v5e: every gap falls in a
    phase span of the serve loop, and the scopes split the busy time."""
    d = _fixture("trace_v5e_spans.json")
    ops = [Op(n, t, t + dur, scope) for n, t, dur, scope in d["ops"]]
    spans = [Span(n, t, t + dur) for n, t, dur in d["spans"]]
    r = pt.reduce([ops], spans)
    assert r["idle_gaps"] and all(
        name.startswith("darth.serve.") for name, _ in r["idle_gaps"])
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    phases = sum(v for k, v in r["idle_by_span"].items()
                 if k.startswith("darth.serve."))
    assert phases >= 0.95 * idle
    assert sum(r["device_by_scope"].values()) == pytest.approx(
        r["busy_s"], rel=1e-6)
    assert {"darth.probe", "darth.merge", "darth.predict"} <= set(
        r["device_by_scope"])
    assert r["busy_s"] == pytest.approx(0.062613962)
    # the batched predictor holds three quarters of the busy time there
    assert r["device_by_scope"]["darth.predict"] == pytest.approx(
        0.047318873)


def test_a_trace_without_program_spans_or_scopes_reads_as_before():
    """The first recorded trace (a program without spans or scopes): the
    busy and window times are trace_reduce's, all device time is "none",
    and no program reading exists."""
    d = _fixture("trace_v5e.json")
    ops = [Op(e.name, e.start_ns, e.end_ns, "none")
           for e in tr.device_ops(d["ops"], d["modules"])]
    spans = [Span(n, t, t + dur) for n, t, dur in d["spans"]]
    r = pt.reduce([ops], spans)
    ref = tr.reduce([tr.device_ops(d["ops"], d["modules"])],
                    [tr.Event(*s[:3]) for s in spans])
    assert r["busy_s"] == pytest.approx(ref["busy_s"])
    assert r["window_s"] == pytest.approx(ref["window_s"])
    assert r["device_by_scope"] == {"none": pytest.approx(ref["busy_s"])}
    assert r["spans_s"] == {} and r["counters"] == {}
    assert [g[0] for g in r["idle_gaps"]] == [g[0] for g in ref["idle_gaps"]]


def test_host_spans_and_counters_of_a_cpu_profile(tmp_path):
    """A real profile of a serve call: the reader keeps the harness's and
    the program's spans, and the counters on `darth.serve`."""
    import jax

    class Server:      # stands in for the program's span and counters
        def serve(self):
            with jax.profiler.TraceAnnotation(
                    "darth.serve", predictor_calls=5, predictor_batches=2,
                    num_slots=4):
                with jax.profiler.TraceAnnotation("darth.serve.refill"):
                    pass
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            Server().serve()
    devices, spans = pt.read_xplane(pt._newest_xplane(str(tmp_path)))
    assert devices == []             # no TPU plane on the CPU
    names = {s.name: s for s in spans}
    assert set(names) == {"window", "darth.serve", "darth.serve.refill"}
    assert names["darth.serve"].stats == {
        "predictor_calls": 5, "predictor_batches": 2, "num_slots": 4}


# -- the readers ------------------------------------------------------------

NEW = ("predictor_share.batch", "probe_share.batch",
       "predictor_due_share.batch", "harvest_share.batch",
       "refill_share.batch")


def _run(trace):
    return harness.Run("cell", {"dim": 128}, {}, "TPU v5 lite", 1.0, {},
                       [], 1.0, None, trace)


def test_readers_need_a_traced_run(tmp_path, monkeypatch):
    for name in NEW:
        assert harness.reader(name)(_run(None)) is None
    # a traced run whose trace directory holds no profile
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    for name in NEW:
        assert harness.reader(name)(_run({"busy_s": 1.0})) is None


def test_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    d = _fixture("trace_v5e.json")
    ops = [Op(e.name, e.start_ns, e.end_ns, "none")
           for e in tr.device_ops(d["ops"], d["modules"])]
    r = pt.reduce([ops], [Span(n, t, t + dur) for n, t, dur in d["spans"]])
    monkeypatch.setattr(pt, "of_run", lambda run: r)
    for name in NEW:
        assert harness.reader(name)(_run({"busy_s": 1.0})) is None


def test_readers_divide_what_the_program_recorded(monkeypatch):
    r = {"busy_s": 2.0, "window_s": 4.0,
         "device_by_scope": {"darth.predict": 0.6, "darth.probe": 0.5,
                             "darth.merge": 0.1, "none": 0.8},
         "spans_s": {"darth.serve": 3.0, "darth.serve.harvest": 0.3,
                     "darth.serve.refill": 0.6},
         "counters": {"predictor_calls": 64, "predictor_batches": 40,
                      "num_slots": 8, "slot_evaluations": 320}}
    monkeypatch.setattr(pt, "of_run", lambda run: r)
    run = _run({"busy_s": 2.0})
    got = {name: harness.reader(name)(run) for name in NEW}
    assert got == pytest.approx({
        "predictor_share.batch": 30.0, "probe_share.batch": 30.0,
        "predictor_due_share.batch": 20.0, "harvest_share.batch": 10.0,
        "refill_share.batch": 20.0})
    assert np.isfinite(list(got.values())).all()


def test_one_reading_of_the_file_serves_every_reader(tmp_path,
                                                     monkeypatch):
    calls = []
    monkeypatch.setattr(pt, "read_xplane",
                        lambda path: calls.append(path) or ([], []))
    (tmp_path / "a.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    pt._reduce_file.cache_clear()
    run = _run({"busy_s": 1.0})
    for name in NEW:
        assert harness.reader(name)(run) is None
    assert len(calls) == 1
    pt._reduce_file.cache_clear()
