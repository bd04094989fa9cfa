"""Observability suite (repro.obs): shared percentile math, the metrics
registry, the trace-span/trajectory-ring contracts, traced-serve parity,
the trace-ledger property (exactly one terminal per admitted query, under
host kills and mid-serve hot-swaps), the mixed-target acceptance scenario
(hosts {1, 2}, ivf + hnsw, hedging + one online compaction swap), the
explain CLI, and the serve loop's profiler spans, device scopes and
predictor counters."""
import json

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro import mutate
from repro.core import api, engines
from repro.index import hnsw, ivf
from repro.obs import explain as explain_lib
from repro.obs import metrics as metrics_lib
from repro.obs import stats as stats_lib
from repro.obs import trace as trace_lib
from repro.serve import DarthServer, TierConfig


# -- obs.stats: the one percentile definition ------------------------------

def test_percentile_empty_and_single_sample():
    assert np.isnan(stats_lib.percentile([], 99))
    assert np.isnan(stats_lib.p50([]))
    assert np.isnan(stats_lib.p99([np.nan, np.inf]))   # non-finite dropped
    # a single sample IS its own p50 / p99 / p01
    for q in (1, 50, 99):
        assert stats_lib.percentile([3.5], q) == 3.5


def test_percentile_conservative_tail_rounding():
    # 2-sample p99 is the max (linear would sit just under it), 2-sample
    # p01 is the min — tails round AWAY from the median
    assert stats_lib.p99([1.0, 10.0]) == 10.0
    assert stats_lib.p01([1.0, 10.0]) == 1.0
    # the median keeps linear interpolation (no conservative direction)
    assert stats_lib.p50([1.0, 10.0]) == pytest.approx(5.5)
    # tails always land ON an observed sample
    xs = list(np.linspace(0.0, 1.0, 7))
    for q in (1, 25, 75, 99):
        assert stats_lib.percentile(xs, q) in xs
    p50, p99 = stats_lib.summarize([2.0, 4.0, 9.0])
    assert p50 == 4.0 and p99 == 9.0


# -- obs.metrics -----------------------------------------------------------

def test_counter_is_monotonic_and_labelled():
    c = metrics_lib.Counter("x_total", "help")
    c.inc()
    c.inc(2.5, host="0")
    assert c.value() == 1.0
    assert c.value(host="0") == 2.5
    assert c.value(host="1") == 0.0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_histogram_buckets_and_shared_summary():
    h = metrics_lib.Histogram("lat_ms", "help", edges=(1.0, 10.0))
    for v in (0.5, 2.0, 3.0, 100.0):
        h.observe(v)
    assert h.count() == 4
    p50, p99 = h.summary()
    assert p50 == 2.5 and p99 == 100.0   # same math as obs.stats
    assert h.count(host="9") == 0


def test_registry_declare_or_get_and_type_collision():
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter("a_total", "h")
    assert reg.counter("a_total") is c          # declare-or-get
    with pytest.raises(TypeError, match="already declared"):
        reg.gauge("a_total")
    g = reg.gauge("g")
    g.set(4.0)
    assert g.value() == 4.0 and np.isnan(g.value(host="1"))
    e1 = reg.event("drift", worst_gap=0.03)
    e2 = reg.event("recal")
    assert e2["seq"] == e1["seq"] + 1           # seq-clocked, ordered


def test_prometheus_exposition_format(tmp_path):
    reg = metrics_lib.serve_metrics(metrics_lib.MetricsRegistry())
    assert metrics_lib.serve_metrics(None) is None
    reg.counter("darth_queries_total").inc(3, outcome="completed")
    reg.histogram("darth_chunk_latency_ms").observe(0.7)
    page = reg.to_prometheus()
    assert '# TYPE darth_queries_total counter' in page
    assert 'darth_queries_total{outcome="completed"} 3' in page
    assert '# TYPE darth_chunk_latency_ms histogram' in page
    assert 'darth_chunk_latency_ms_bucket{le="1"} 1' in page
    assert 'darth_chunk_latency_ms_bucket{le="+Inf"} 1' in page
    assert 'darth_chunk_latency_ms_count 1' in page
    # pre-declared families appear even with zero traffic
    assert "darth_harvest_recall" in page
    reg.write_prometheus(str(tmp_path / "m.prom"))
    reg.event("swap", epoch=1)
    reg.write_events(str(tmp_path / "ev.jsonl"), append=False)
    ev = [json.loads(x) for x in
          (tmp_path / "ev.jsonl").read_text().splitlines()]
    assert ev[0]["kind"] == "swap" and ev[0]["epoch"] == 1


# -- obs.trace: ring + tracer contracts ------------------------------------

def test_trajectory_ring_record_and_window():
    traj = trace_lib.traj_init(2, 4)
    assert traj.shape == (2, 4)
    assert (np.asarray(traj) == trace_lib.NO_PREDICTION).all()
    # step g lands at column (g - 1) % cap
    for g in range(1, 7):
        r = jnp.full((2,), g / 10.0, jnp.float32)
        traj = trace_lib.traj_record(traj, jnp.int32(g), r)
    row = np.asarray(traj)[0]
    # steps 5, 6 overwrote columns 0, 1: ring holds [.5, .6, .3, .4]
    np.testing.assert_allclose(row, [0.5, 0.6, 0.3, 0.4], atol=1e-6)
    # admitted at step 2, harvested at step 6 -> steps 3..6, oldest first
    w, trunc = trace_lib.traj_window(row, 2, 6, 0)
    np.testing.assert_allclose(w, [0.3, 0.4, 0.5, 0.6], atol=1e-6)
    assert not trunc
    # window longer than the ring keeps the most recent cap entries
    # (unrolled by the cursor) and reports the dropped prefix
    w, trunc = trace_lib.traj_window(row, 0, 6, 0)
    np.testing.assert_allclose(w, [0.3, 0.4, 0.5, 0.6], atol=1e-6)
    assert trunc
    assert trace_lib.traj_window(row, 6, 6, 0) == ([], False)
    # base offset: ring re-initialized at engine step 10 counts its
    # columns from there (device steps are chunk-local after a rebuild)
    t2 = trace_lib.traj_init(1, 4)
    for s, v in ((1, 0.1), (2, 0.2)):
        t2 = trace_lib.traj_record(t2, jnp.int32(s),
                                   jnp.full((1,), v, jnp.float32))
    row2 = np.asarray(t2)[0]
    np.testing.assert_allclose(trace_lib.traj_window(row2, 10, 12, 10)[0],
                               [0.1, 0.2], atol=1e-6)
    np.testing.assert_allclose(trace_lib.traj_window(row2, 11, 12, 10)[0],
                               [0.2], atol=1e-6)


def test_trajectory_window_outliving_ring_is_exact_suffix():
    """Regression: a query served for more than traj_cap steps must
    drain the most recent cap predictions IN STEP ORDER (unrolled by
    the cursor, not raw ring order) and be flagged truncated."""
    cap = 5
    traj = trace_lib.traj_init(1, cap)
    full = []
    for g in range(1, 14):                     # 13 steps >> cap
        v = g / 100.0
        full.append(v)
        traj = trace_lib.traj_record(traj, jnp.int32(g),
                                     jnp.full((1,), v, jnp.float32))
        row = np.asarray(traj)[0]
        w, trunc = trace_lib.traj_window(row, 0, g, 0)
        # the drained window is always the exact most-recent suffix of
        # the true step series, regardless of wrap count
        np.testing.assert_allclose(w, full[-cap:], atol=1e-6)
        assert trunc == (g > cap)
        assert w[-1] == pytest.approx(v)


def test_tracer_exactly_once_and_reason_taxonomy():
    tr = trace_lib.Tracer()
    tr.begin()
    with pytest.raises(ValueError, match="unknown termination reason"):
        tr.terminal(0, "gave_up")
    tr.event("admit", qid=0, host=1, step=0)
    tr.terminal(0, "interval_met", step=4, r_pred=0.93)
    with pytest.raises(RuntimeError, match="exactly-once"):
        tr.terminal(0, "engine_exhausted")
    # the one sanctioned mutation: a hedge upgrade
    sp = tr.upgrade_terminal(0, step=6, r_pred=0.97)
    assert sp.attrs["upgraded"] and sp.attrs["r_pred"] == 0.97
    assert sp.step == 6
    spans = tr.finish()
    assert [s.seq for s in spans] == sorted(s.seq for s in spans)
    assert tr.terminals()[0].attrs["reason"] == "interval_met"
    with pytest.raises(ValueError, match="traj_cap"):
        trace_lib.Tracer(traj_cap=0)


def test_trace_jsonl_roundtrip_and_serve_filter(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tr = trace_lib.Tracer(path)
    for reason in ("interval_met", "budget_truncated"):
        tr.begin()
        tr.event("admit", qid=7, host=0, step=0)
        tr.terminal(7, reason, step=3)
        tr.finish()
    last = trace_lib.load_trace(path)          # default: LAST serve
    assert {s["serve"] for s in last} == {2}
    assert [s for s in last if s["kind"] == "terminal"][0]["reason"] \
        == "budget_truncated"
    first = trace_lib.load_trace(path, serve=1)
    assert [s for s in first if s["kind"] == "terminal"][0]["reason"] \
        == "interval_met"
    assert trace_lib.load_trace(str(tmp_path / "t.jsonl")) != []


# -- served integration ----------------------------------------------------

@pytest.fixture(scope="module")
def obs_setup():
    from repro.data import vectors
    ds = vectors.make_dataset(n=2000, d=16, num_learn=192, num_queries=64,
                              clusters=16, cluster_std=1.0, seed=4)
    index = ivf.build(ds.base, nlist=16, seed=4)
    eng = engines.ivf_engine(index, k=10, nprobe=16)
    d = api.Darth(make_engine=lambda **kw: engines.ivf_engine(index, **kw),
                  engine=eng)
    d.fit(jnp.asarray(ds.learn), jnp.asarray(ds.base), batch=128)
    return ds, index, d


def _ledger_reasons(results, terminals):
    """Cross-check every terminal reason against the results ledger."""
    for qid, span in terminals.items():
        reason = span.attrs["reason"]
        if results[qid] is not None:
            assert reason in ("interval_met", "engine_exhausted",
                              "budget_truncated", "host_killed"), \
                (qid, reason)
        else:
            assert reason in ("shed", "abandoned"), (qid, reason)


def _check_trajectories(terminals):
    """Terminal trajectory's final value must equal the harvested slot's
    prediction (the device ring and the host fetch agree)."""
    checked = 0
    for span in terminals.values():
        traj = span.attrs.get("trajectory")
        rp = span.attrs.get("r_pred")
        if traj and rp is not None:
            assert traj[-1] == pytest.approx(rp, abs=1e-6), span
            checked += 1
    return checked


def test_traced_serve_matches_untraced_and_closes_every_query(obs_setup):
    """Tracing must be a pure observer: byte-identical results/ndis vs
    the untraced server, plus exactly one terminal span per query whose
    trajectory ends at the harvested slot's prediction."""
    ds, index, d = obs_setup
    rts = np.tile([0.7, 0.9, 0.8, 0.95], 16).astype(np.float32)

    ref_server = DarthServer(d.engine, d.trained.predictor,
                             d.interval_for_target, num_slots=8,
                             steps_per_sync=2)
    ref, ref_stats = ref_server.serve(ds.queries, rts)

    tracer = trace_lib.Tracer(traj_cap=32)
    server = DarthServer(d.engine, d.trained.predictor,
                         d.interval_for_target, num_slots=8,
                         steps_per_sync=2, tracer=tracer)
    res, stats = server.serve(ds.queries, rts)
    assert stats.completed == ref_stats.completed == 64
    assert stats.ndis_harvested == ref_stats.ndis_harvested
    for a, b in zip(ref, res):
        np.testing.assert_allclose(a[0], b[0], atol=0)
        np.testing.assert_array_equal(a[1], b[1])

    terms = tracer.terminals()
    assert sorted(terms) == list(range(64))        # every query, once
    for qid, span in terms.items():
        assert span.attrs["reason"] in ("interval_met", "engine_exhausted")
        assert span.attrs["target"] == pytest.approx(float(rts[qid]))
    assert _check_trajectories(terms) == 64
    # refill splices after the first fill leave admit spans marked so
    admits = [s for s in tracer.last_spans if s.kind == "admit"]
    assert len(admits) == 64 and stats.refills > 0
    assert any(s.attrs.get("refill") for s in admits)


def test_served_trajectory_outliving_ring(obs_setup):
    """Regression (queries served > traj_cap steps): the drained
    trajectory must be the exact most-recent suffix of the full series
    (cursor-unrolled, in step order), flagged truncated, and still end
    at the harvested r_pred; explain marks the dropped prefix."""
    ds, index, d = obs_setup
    rts = np.full((64,), 0.95, np.float32)    # high target -> long lives
    cap = 2

    big = trace_lib.Tracer(traj_cap=64)       # never wraps here
    DarthServer(d.engine, d.trained.predictor, d.interval_for_target,
                num_slots=8, steps_per_sync=3,
                tracer=big).serve(ds.queries, rts)
    small = trace_lib.Tracer(traj_cap=cap)
    DarthServer(d.engine, d.trained.predictor, d.interval_for_target,
                num_slots=8, steps_per_sync=3,
                tracer=small).serve(ds.queries, rts)

    terms_small, terms_big = small.terminals(), big.terminals()
    truncated_qids = []
    for qid, span in terms_small.items():
        traj = span.attrs.get("trajectory")
        if traj is None:
            continue
        assert len(traj) <= cap
        ref = terms_big[qid].attrs["trajectory"]
        lived = span.step - span.attrs["admit_step"]
        # exact suffix of the unwrapped reference trajectory
        np.testing.assert_allclose(traj, ref[-len(traj):], atol=0)
        assert bool(span.attrs.get("trajectory_truncated")) == \
            (lived > cap), span
        rp = span.attrs.get("r_pred")
        if traj and rp is not None:
            assert traj[-1] == pytest.approx(rp, abs=1e-6)
        if span.attrs.get("trajectory_truncated"):
            truncated_qids.append(qid)
    assert truncated_qids, "workload never outlived the ring (cap=2?)"

    from repro.obs import explain as explain_lib
    story = explain_lib.explain(small.last_spans, qid=truncated_qids[0])
    assert "…" in story and "last " in story


def test_single_chunk_serve_has_degenerate_percentiles(obs_setup):
    """ServeStats edge case: one chunk -> one latency sample, so p50 and
    p99 are that sample (NaN/interp regressions pinned by obs.stats)."""
    ds, index, d = obs_setup
    server = DarthServer(d.engine, d.trained.predictor,
                         d.interval_for_target, num_slots=8,
                         steps_per_sync=2)
    _, stats = server.serve(ds.queries[:8],
                            np.full((8,), 0.9, np.float32),
                            max_engine_steps=2)
    assert np.isfinite(stats.chunk_ms_p50)
    assert stats.chunk_ms_p50 == stats.chunk_ms_p99


@settings(deadline=None, max_examples=5)
@given(hosts=st.sampled_from([1, 2, 4]), budget=st.sampled_from([0, 4]),
       kill=st.booleans(), kill_step=st.integers(2, 6),
       swap_at=st.integers(0, 2))
def test_trace_ledger_exactly_once_property(obs_setup, hosts, budget,
                                            kill, kill_step, swap_at):
    """Satellite property: every admitted query id appears in the trace
    with EXACTLY one terminal span whose reason is consistent with the
    results ledger (served / shed / abandoned) — including under
    kill_hosts fault injection and a mid-serve request_swap."""
    ds, index, d = obs_setup
    n = 64
    rts = np.tile([0.8, 0.9], n // 2).astype(np.float32)
    tracer = trace_lib.Tracer(traj_cap=16)
    server = DarthServer(d.engine, d.trained.predictor,
                         d.interval_for_target, num_slots=8,
                         steps_per_sync=2, hosts=hosts, tracer=tracer)
    kill_hosts = {1: kill_step} if kill and hosts > 1 else {}
    seen = {"n": 0}

    def on_boundary(srv):
        seen["n"] += 1
        if swap_at and seen["n"] == swap_at and not srv.swap_pending:
            srv.request_swap(engines.ivf_engine(index, k=10, nprobe=16),
                             contents_only=True)

    results, stats = server.serve(
        ds.queries[:n], rts, max_engine_steps=budget or 10_000,
        kill_hosts=kill_hosts,
        on_boundary=on_boundary if swap_at else None)

    terms = tracer.terminals()
    assert sorted(terms) == list(range(n))         # exactly once, all n
    _ledger_reasons(results, terms)
    reasons = [s.attrs["reason"] for s in terms.values()]
    assert stats.completed == sum(
        r in ("interval_met", "engine_exhausted") for r in reasons)
    assert stats.truncated == sum(
        r in ("budget_truncated", "host_killed") for r in reasons)
    assert sum(h.abandoned for h in stats.hosts) == reasons.count(
        "abandoned")
    # killed hosts close their in-flight queries as host_killed
    if kill_hosts and any(h.killed and h.truncated for h in stats.hosts):
        assert "host_killed" in reasons
    # a swap that applied left its server-level breadcrumbs
    if stats.swaps:
        kinds = [s.kind for s in tracer.last_spans]
        assert "swap_staged" in kinds and "swap_applied" in kinds


@pytest.mark.parametrize("kind,hosts", [("ivf", 1), ("ivf", 2),
                                        ("hnsw", 2)])
def test_acceptance_hedged_compacting_serve_closes_every_query(
        obs_setup, kind, hosts):
    """The PR acceptance bar: a mixed-target serve on hosts {1, 2} with
    both engine families, hedging tiers and ONE online compaction swap
    yields exactly one terminal span per query, with a correct reason
    and a trajectory whose final value matches the harvested slot's
    prediction; the compaction lifecycle is visible in the trace."""
    ds, _, _ = obs_setup
    if kind == "ivf":
        index = ivf.build(ds.base, nlist=16, seed=4)
        make = lambda mut, **kw: engines.mutable_engine(        # noqa: E731
            engines.ivf_engine(mut.base, k=10, nprobe=16), mut.delta)
    else:
        index = hnsw.build(ds.base, m=8, passes=1, ef_construction=32,
                           seed=4)
        make = lambda mut, **kw: engines.mutable_engine(        # noqa: E731
            engines.hnsw_engine(mut.base, k=10, ef=32), mut.delta)
    mut = mutate.MutableIndex(index, capacity=256)
    d = api.Darth(make_engine=lambda **kw: make(mut, **kw),
                  engine=make(mut))
    d.fit(jnp.asarray(ds.learn), jnp.asarray(ds.base), batch=128)

    tracer = trace_lib.Tracer(traj_cap=32)
    tiers = TierConfig(hard_quantile=0.75, hard_slot_fraction=0.25,
                       hedge=True)
    server = DarthServer(d.engine, d.trained.predictor,
                         d.interval_for_target, num_slots=8,
                         steps_per_sync=2, hosts=hosts, tiers=tiers,
                         tracer=tracer)
    state = {"swapped": False}

    def on_boundary(srv):
        if srv.swap_pending or state["swapped"]:
            return
        if not mut.compacting:
            mut.begin_compaction()
            srv.tracer.event("compact_begin", step=srv.boundary_step,
                             epoch=srv.engine_epoch)
        elif mut.compact_tick():
            mut.swap_compaction()
            srv.tracer.event("compact_swap", step=srv.boundary_step,
                             epoch=srv.engine_epoch)
            srv.request_swap(make(mut), contents_only=True)
            state["swapped"] = True

    n = ds.queries.shape[0]
    rts = np.tile([0.7, 0.9, 0.8, 0.95], n // 4).astype(np.float32)
    results, stats = server.serve(ds.queries, rts,
                                  on_boundary=on_boundary)
    assert stats.completed == n and all(r is not None for r in results)
    assert state["swapped"] and stats.swaps == 1

    terms = tracer.terminals()
    assert sorted(terms) == list(range(n))         # exactly one each
    _ledger_reasons(results, terms)
    assert _check_trajectories(terms) == n
    assert stats.hedged >= stats.hedge_upgrades + stats.hedge_epoch_dropped
    kinds = [s.kind for s in tracer.last_spans]
    for k in ("compact_begin", "compact_swap", "swap_staged",
              "swap_applied"):
        assert k in kinds, k
    # some query's flight window crossed the server-level swap events
    crossed = [explain_lib.query_story(tracer.last_spans, q)["crossed"]
               for q in range(n)]
    assert any(crossed)


def test_shed_queries_get_shed_terminals(obs_setup):
    """Overload shedding closes refused queries with reason 'shed' (they
    never held a slot) and the trace agrees with HostStats.shed_ids."""
    ds, index, d = obs_setup
    tracer = trace_lib.Tracer(traj_cap=16)
    tiers = TierConfig(hard_quantile=0.5, hard_slot_fraction=0.25,
                       max_queue=2, overload="shed")
    server = DarthServer(d.engine, d.trained.predictor,
                         d.interval_for_target, num_slots=8,
                         steps_per_sync=2, tiers=tiers, tracer=tracer)
    results, stats = server.serve(ds.queries,
                                  np.full((64,), 0.9, np.float32))
    assert stats.shed > 0
    terms = tracer.terminals()
    assert sorted(terms) == list(range(64))
    shed_ids = sorted(i for h in stats.hosts for i in h.shed_ids)
    traced_shed = sorted(q for q, s in terms.items()
                         if s.attrs["reason"] == "shed")
    assert traced_shed == shed_ids
    for q in traced_shed:
        assert results[q] is None
        assert "closed without holding a slot" in explain_lib.explain(
            tracer.last_spans, qid=q)


def test_serve_exports_metrics_matching_stats(obs_setup):
    """Metrics work tracer-less: terminal-outcome counters equal the
    ServeStats ledger and the exposition page renders every family."""
    ds, index, d = obs_setup
    reg = metrics_lib.MetricsRegistry()
    server = DarthServer(d.engine, d.trained.predictor,
                         d.interval_for_target, num_slots=8,
                         steps_per_sync=2, hosts=2, metrics=reg)
    _, stats = server.serve(ds.queries, np.full((64,), 0.9, np.float32))
    q = reg.counter("darth_queries_total")
    assert q.value(outcome="completed") == stats.completed == 64
    assert q.value(outcome="truncated") == 0
    lat = reg.histogram("darth_chunk_latency_ms")
    assert lat.count() > 0
    assert reg.histogram("darth_harvest_recall").count() > 0
    assert reg.histogram("darth_service_steps").count() == 64
    assert reg.counter("darth_refills_total").value(host="0") > 0
    assert reg.gauge("darth_engine_epoch").value() == server.engine_epoch
    page = reg.to_prometheus()
    assert 'darth_queries_total{outcome="completed"} 64' in page


def test_compaction_and_drift_metrics_events(obs_setup):
    """mutate.MutableIndex and the drift monitor land their lifecycle
    in an attached registry: compact begin/tick/swap events + the
    compaction counter, drift events + the worst-gap gauge."""
    from repro.mutate import monitor as monitor_lib

    ds, index, d = obs_setup
    reg = metrics_lib.MetricsRegistry()
    mut = mutate.MutableIndex(ivf.build(ds.base, nlist=16, seed=4),
                              capacity=256)
    mut.attach_metrics(reg)
    mut.begin_compaction()
    while not mut.compact_tick():
        pass
    mut.swap_compaction()
    kinds = [e["kind"] for e in reg.events]
    assert kinds[0] == "compact_begin" and kinds[-1] == "compact_swap"
    assert "compact_tick" in kinds
    assert reg.counter("darth_compactions_total").value() == 1

    mon = monitor_lib.RecalibrationMonitor(mut, d, metrics=reg)
    server = DarthServer(d.engine, d.trained.predictor,
                         d.interval_for_target, num_slots=8,
                         steps_per_sync=2)
    res, _ = server.serve(ds.queries[:16],
                          np.full((16,), 0.9, np.float32))
    mon.observe(ds.queries[:16], np.full((16,), 0.9, np.float32),
                np.stack([r[1] for r in res]))
    rep = mon.drift()
    drift_ev = [e for e in reg.events if e["kind"] == "drift"]
    assert drift_ev and drift_ev[-1]["num_queries"] == 16
    assert reg.gauge("darth_drift_worst_gap").value() == pytest.approx(
        rep.worst_gap)


# -- explain ---------------------------------------------------------------

def test_explain_story_and_cli(obs_setup, tmp_path, capsys):
    ds, index, d = obs_setup
    path = str(tmp_path / "trace.jsonl")
    tracer = trace_lib.Tracer(path, traj_cap=32, label="unit")
    server = DarthServer(d.engine, d.trained.predictor,
                         d.interval_for_target, num_slots=8,
                         steps_per_sync=2, tracer=tracer)
    rts = np.tile([0.8, 0.95], 32).astype(np.float32)
    server.serve(ds.queries, rts)

    story = explain_lib.query_story(tracer.last_spans, 5)
    assert story["qid"] == 5 and story["admissions"]
    assert story["terminal"]["reason"] in ("interval_met",
                                           "engine_exhausted")
    with pytest.raises(KeyError, match="no terminal span"):
        explain_lib.query_story(tracer.last_spans, 999)

    text = explain_lib.explain(tracer.last_spans, qid=5)
    assert text.startswith("query 5:") and "admitted on host" in text
    assert "trajectory" in text
    # default pick: the worst final predicted recall among terminals
    worst = min(tracer.terminals().values(),
                key=lambda s: s.attrs.get("r_pred", float("inf")))
    assert explain_lib.explain(tracer.last_spans).startswith(
        f"query {worst.qid}:")
    roll = explain_lib.summary(tracer.last_spans)
    assert "64 queries" in roll and "p50/p99" in roll

    # CLI round-trips through the JSONL file the tracer appended
    assert explain_lib.main([path, "--summary"]) == 0
    assert "64 queries" in capsys.readouterr().out
    assert explain_lib.main([path, "--qid", "5"]) == 0
    assert "query 5:" in capsys.readouterr().out
    assert explain_lib.main([path]) == 0
    assert f"query {worst.qid}:" in capsys.readouterr().out
    assert explain_lib.explain([]) == \
        "trace holds no terminal spans (nothing was served?)"


# -- profiler spans, device scopes, predictor counters ---------------------

def _server(d, **kw):
    return DarthServer(d.engine, d.trained.predictor, d.interval_for_target,
                       num_slots=8, steps_per_sync=2, **kw)


def _profiled_serve(server, queries, rts, trace_dir):
    """Serve under jax.profiler.trace; (results, stats, host spans named
    darth.serve*, as (name, start_ns, end_ns, stats))."""
    import glob
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(trace_dir)):
        res, stats = server.serve(queries, rts)
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(trace_lib.SERVE_SPAN)]
    return res, stats, spans


def test_serve_emits_profiler_spans_nested_in_the_call(obs_setup, tmp_path):
    """One darth.serve span per call, carrying the predictor counters;
    dispatch and sync once per chunk; every phase inside the call."""
    ds, index, d = obs_setup
    rts = np.tile([0.8, 0.9, 0.95, 0.7], 16).astype(np.float32)
    res, stats, spans = _profiled_serve(_server(d), ds.queries, rts,
                                        tmp_path)
    names = [s[0] for s in spans]
    assert set(names) <= set(trace_lib.SERVE_SPANS)
    assert names.count("darth.serve") == 1
    chunks = stats.engine_steps // 2
    assert names.count("darth.serve.dispatch") == chunks
    assert names.count("darth.serve.sync") == chunks
    assert names.count("darth.serve.admit") == 1
    assert names.count("darth.serve.finish") == 1
    for phase in ("harvest", "hook", "refill"):
        assert names.count(f"darth.serve.{phase}") >= chunks - 1
    call = next(s for s in spans if s[0] == "darth.serve")
    for name, t0, t1, _ in spans:
        assert call[1] <= t0 <= t1 <= call[2], name
    assert call[3] == {"predictor_calls": stats.predictor_calls,
                       "predictor_batches": stats.predictor_batches,
                       "num_slots": 8}
    assert stats.completed == 64 and all(r is not None for r in res)


def test_run_chunk_carries_device_scopes(obs_setup):
    """The probe step, its top-k merge and the predictor branch carry
    their named scopes in the chunk program's op metadata."""
    ds, index, d = obs_setup
    server = _server(d)
    rt = np.full((8,), 0.9, np.float32)
    p = d.interval_for_target(rt)
    st = server._init_chunk(index, jnp.asarray(ds.queries[:8]),
                            jnp.asarray(p.ipi), jnp.asarray(p.mpi))
    lowered = server._run_chunk.lower(index, st, jnp.asarray(rt),
                                      jnp.asarray(p.ipi), jnp.asarray(p.mpi))
    text = lowered.as_text(debug_info=True)
    for scope in trace_lib.DEVICE_SCOPES:
        assert f'loc("{scope}/' in text or f"/{scope}/" in text, scope
    # the compiled ops' op_name paths nest the merge inside the probe
    hlo = lowered.compile().as_text()
    assert 'op_name="jit(run_chunk)/while/body/' in hlo
    assert "/darth.probe/jit(probe_step)/darth.merge/" in hlo
    assert "/cond/branch_1_fun/darth.predict/" in hlo


def test_predictor_counters_match_the_trace_and_untraced_serve(obs_setup):
    """predictor_calls is the sum of the queries' npred; the batched
    predictor ran on at least as many steps as the busiest query and on
    no more than every step; tracing changes neither counter nor any
    result."""
    ds, index, d = obs_setup
    rts = np.tile([0.7, 0.9, 0.8, 0.95], 16).astype(np.float32)
    ref, ref_stats = _server(d).serve(ds.queries, rts)
    tracer = trace_lib.Tracer(traj_cap=32)
    res, stats = _server(d, tracer=tracer).serve(ds.queries, rts)
    npred = [s.attrs["npred"] for s in tracer.terminals().values()]
    assert len(npred) == 64
    assert stats.predictor_calls == sum(npred) > 0
    assert max(npred) <= stats.predictor_batches <= stats.engine_steps
    assert (ref_stats.predictor_calls, ref_stats.predictor_batches) == \
        (stats.predictor_calls, stats.predictor_batches)
    assert sum(h.predictor_calls for h in stats.hosts) == \
        stats.predictor_calls
    for a, b in zip(ref, res):
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])


def test_predictor_batches_survive_a_drained_swap(obs_setup):
    """A drained swap discards the chunk state, and with it the
    on-device batch count: the host adds what each state had counted."""
    import jax

    ds, index, d = obs_setup
    server = _server(d)
    seen = {}                         # engine epoch -> last nbatch seen

    def hook(srv):
        seen[srv.engine_epoch] = int(jax.device_get(
            srv.chunk_state.nbatch))
        if srv.boundary_step == 4:
            srv.request_swap(engine=srv.engine)
    _, stats = server.serve(ds.queries, np.full((64,), 0.9, np.float32),
                            on_boundary=hook)
    assert stats.swaps == 1 and stats.completed == 64
    assert len(seen) == 2 and min(seen.values()) > 0
    assert stats.predictor_batches == sum(seen.values())


def test_untraced_serve_fetches_no_more_per_boundary(obs_setup,
                                                     monkeypatch):
    """Per chunk boundary an untraced serve fetches the active mask, and
    at a harvest topk_d, topk_i and (ndis, npred) together; once per call
    it reads the batched-predictor count."""
    import jax
    from repro.serve import engine as engine_lib

    ds, index, d = obs_setup
    gets, harvests = [0], [0]
    real_get, real_harvest = jax.device_get, engine_lib._HostSlots.harvest

    def get(x):
        gets[0] += 1
        return real_get(x)

    def harvest(self, *a, **kw):
        harvests[0] += 1
        return real_harvest(self, *a, **kw)
    monkeypatch.setattr(jax, "device_get", get)
    monkeypatch.setattr(engine_lib._HostSlots, "harvest", harvest)
    _, stats = _server(d).serve(ds.queries, np.full((64,), 0.9, np.float32))
    chunks = stats.engine_steps // 2
    assert gets[0] == chunks + 3 * harvests[0] + 1
