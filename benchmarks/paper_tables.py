"""One function per paper table/figure (deliverable d). Each returns
(rows, headline) where rows are dicts for the CSV and headline is the
paper-comparable number."""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np
import jax.numpy as jnp

from benchmarks import common
from repro import gbdt
from repro.core import (baselines, darth_search, engines, features,
                        intervals, metrics, training)
from repro.data import vectors
from repro.index import flat
from repro.core.predictor import regression_metrics

Rows = List[Dict]


def _run_darth(d, q, rt):
    t0 = time.time()
    dd, ii, st = d.search(q, rt)
    wall = time.time() - t0
    return dd, ii, st, wall


# --- Fig 6 / Fig 19: recall + speedup vs target, both indexes -------------

def fig6_darth_speedups(index_kind: str = "hnsw") -> Tuple[Rows, str]:
    b = common.setup()
    d = b.darth_hnsw if index_kind == "hnsw" else b.darth_ivf
    q = jnp.asarray(b.ds.queries)
    _, _, plain = d.search_plain(q)
    plain_nd = float(np.asarray(plain.ndis).mean())
    t0 = time.time()
    d.search_plain(q)
    plain_wall = time.time() - t0
    rows = []
    speeds = []
    for rt in common.TARGETS:
        dd, ii, st, wall = _run_darth(d, q, rt)
        rec = float(np.asarray(flat.recall_at_k(ii, jnp.asarray(b.gt["i"])
                                                )).mean())
        nd = float(np.asarray(st.inner.ndis).mean())
        speed = plain_nd / max(nd, 1)
        speeds.append(speed)
        rows.append({"target": rt, "recall": round(rec, 4),
                     "mean_ndis": round(nd, 1),
                     "speedup_dists": round(speed, 2),
                     "speedup_wall": round(plain_wall / max(wall, 1e-9), 2),
                     "met": rec >= rt - 0.01,
                     "npred": round(float(np.asarray(st.npred).mean()), 1)})
    headline = (f"speedup(dists) max={max(speeds):.1f}x "
                f"avg={np.mean(speeds):.1f}x median={np.median(speeds):.1f}x")
    return rows, headline


# --- Fig 8: optimality of termination points ------------------------------

def fig8_optimality(index_kind: str = "ivf") -> Tuple[Rows, str]:
    b = common.setup()
    d = b.darth_hnsw if index_kind == "hnsw" else b.darth_ivf
    q = jnp.asarray(b.ds.queries)
    gt_i = jnp.asarray(b.gt["i"])
    # per-query oracle: log the test queries' search, find first step >= Rt
    log = training.generate_observations(d.engine, q, gt_i, batch=512)
    rows = []
    ratios = []
    for rt in common.TARGETS:
        oracle = intervals.dists_to_target(log.recall, log.ndis, log.valid,
                                           rt)
        _, _, st, _ = _run_darth(d, q, rt)
        actual = np.asarray(st.inner.ndis, np.float64)
        ratio = float(actual.mean() / max(oracle.mean(), 1.0))
        ratios.append(ratio)
        rows.append({"target": rt, "oracle_ndis": round(oracle.mean(), 1),
                     "darth_ndis": round(actual.mean(), 1),
                     "overhead": round(ratio - 1.0, 3)})
    headline = f"mean dists vs oracle: +{100*(np.mean(ratios)-1):.0f}%"
    return rows, headline


# --- Table 5: recall predictor quality ------------------------------------

def table5_predictor_quality() -> Tuple[Rows, str]:
    b = common.setup()
    rows = []
    for name, d in (("ivf", b.darth_ivf), ("hnsw", b.darth_hnsw)):
        if d is None:
            continue
        m = d.trained.metrics
        rows.append({"index": name, "mse": round(m["mse"], 5),
                     "mae": round(m["mae"], 5), "r2": round(m["r2"], 3)})
    headline = f"ivf mse={rows[0]['mse']} r2={rows[0]['r2']}"
    return rows, headline


# --- Table 4: training cost -------------------------------------------------

def table4_training_cost() -> Tuple[Rows, str]:
    b = common.setup()
    rows = []
    for name, d in (("ivf", b.darth_ivf), ("hnsw", b.darth_hnsw)):
        if d is None:
            continue
        tr = d.trained
        rows.append({
            "index": name,
            "gen_seconds": round(b.build_seconds.get(f"darth_{name}_fit", 0.0)
                                 - tr.train_seconds, 1),
            "train_seconds": round(tr.train_seconds, 1),
            "train_samples": tr.num_samples,
            "index_build_seconds": round(
                b.build_seconds.get(f"{name}_build", 0.0), 1),
        })
    headline = (f"fit<<build: train={rows[0]['train_seconds']}s vs "
                f"build={rows[0]['index_build_seconds']}s")
    return rows, headline


# --- Fig 5: adaptive vs static intervals, heuristic vs tuned ---------------

def fig5_interval_ablation() -> Tuple[Rows, str]:
    b = common.setup()
    d = b.darth_ivf
    q = jnp.asarray(b.ds.queries)
    rt = 0.90
    dr = d.trained.dists_rt[rt]
    variants = {
        "adaptive_heuristic": intervals.heuristic_params(dr),
        "adaptive_static": intervals.static_params(dr, divisor=4.0),
        "static_small": intervals.IntervalParams(ipi=dr / 10, mpi=dr / 10),
        "static_large": intervals.IntervalParams(ipi=dr, mpi=dr),
    }
    rows = []
    for name, p in variants.items():
        st = darth_search.darth_search(d.engine, q, rt,
                                       d.trained.predictor, p)
        rec = float(np.asarray(flat.recall_at_k(
            d.engine.topk_i(st.inner), jnp.asarray(b.gt["i"]))).mean())
        rows.append({"variant": name,
                     "recall": round(rec, 4),
                     "mean_ndis": round(float(np.asarray(st.inner.ndis)
                                              .mean()), 1),
                     "npred": round(float(np.asarray(st.npred).mean()), 1)})
    base = [r for r in rows if r["variant"] == "adaptive_heuristic"][0]
    headline = (f"adaptive-heuristic ndis={base['mean_ndis']} "
                f"npred={base['npred']}")
    return rows, headline


# --- Fig 10 + 12-16: competitor comparison ---------------------------------

def fig10_competitors(r_target: float = 0.95) -> Tuple[Rows, str]:
    b = common.setup()
    d = b.darth_ivf
    eng = d.engine
    q = jnp.asarray(b.ds.queries)
    gt_i = jnp.asarray(b.gt["i"])
    x = jnp.asarray(b.ds.base)

    # validation split from learn pool for competitor tuning
    q_val = jnp.asarray(b.ds.learn[:512])
    _, gt_val = flat.search(q_val, x, common.K)

    # training log (shared with LAET)
    q_tr = jnp.asarray(b.ds.learn[512:1536])
    _, gt_tr = flat.search(q_tr, x, common.K)
    log = training.generate_observations(eng, q_tr, gt_tr, batch=512)

    runs = {}
    # DARTH
    _, ii, st, _ = _run_darth(d, q, r_target)
    runs["darth"] = (eng.topk_d(st.inner), ii)
    # Baseline: fixed dists_Rt budget
    drt = float(np.mean(intervals.dists_to_target(log.recall, log.ndis,
                                                  log.valid, r_target)))
    inner = darth_search.budget_search(eng, q, drt)
    runs["baseline"] = (eng.topk_d(inner), eng.topk_i(inner))
    # REM: recall -> nprobe mapping
    rem = baselines.fit_rem(
        lambda p: engines.ivf_engine(b.ivf_index, k=common.K, nprobe=p),
        q_val, gt_val, param_grid=[4, 8, 16, 32, 64, 96, 128, 192],
        targets=[r_target])
    eng_rem = engines.ivf_engine(b.ivf_index, k=common.K,
                                 nprobe=rem.mapping[r_target])
    inner = darth_search.plain_search(eng_rem, q)
    runs["rem"] = (eng_rem.topk_d(inner), eng_rem.topk_i(inner))
    # LAET
    laet = baselines.fit_laet(log, n0=2)
    laet = baselines.tune_laet(laet, eng, q_val, gt_val,
                               targets=[r_target], steps=6)
    inner = baselines.laet_search(laet, eng, q,
                                  laet.multipliers[r_target])
    runs["laet"] = (eng.topk_d(inner), eng.topk_i(inner))

    rows = []
    for name, (dd, ii) in runs.items():
        m = metrics.summarize(np.asarray(dd), np.asarray(ii),
                              b.gt["d"], b.gt["i"], b.gt["wide_i"], r_target)
        m = {k: round(v, 4) for k, v in m.items()}
        rows.append({"method": name, **m})
    darth_row = [r for r in rows if r["method"] == "darth"][0]
    best_rqut = min(r["rqut"] for r in rows)
    headline = (f"DARTH rqut={darth_row['rqut']} (best={best_rqut}), "
                f"rde={darth_row['rde']}")
    return rows, headline


# --- Fig 11: robustness on noisy (harder) workloads -------------------------

def fig11_hardness(r_target: float = 0.90) -> Tuple[Rows, str]:
    b = common.setup()
    d = b.darth_ivf
    eng = d.engine
    x = jnp.asarray(b.ds.base)
    q_val = jnp.asarray(b.ds.learn[:512])
    _, gt_val = flat.search(q_val, x, common.K)
    q_tr = jnp.asarray(b.ds.learn[512:1536])
    _, gt_tr = flat.search(q_tr, x, common.K)
    log = training.generate_observations(eng, q_tr, gt_tr, batch=512)
    drt = float(np.mean(intervals.dists_to_target(log.recall, log.ndis,
                                                  log.valid, r_target)))
    rem = baselines.fit_rem(
        lambda p: engines.ivf_engine(b.ivf_index, k=common.K, nprobe=p),
        q_val, gt_val, param_grid=[4, 8, 16, 32, 64, 96, 128, 192],
        targets=[r_target])
    laet = baselines.fit_laet(log, n0=2)
    laet = baselines.tune_laet(laet, eng, q_val, gt_val, targets=[r_target],
                               steps=6)

    rows = []
    # sigma^2 = pct * ||q|| (paper formula) is norm-scale dependent; on the
    # unit-ish synthetic norms the paper's 1-30% is imperceptible, so the
    # sweep uses pct values that span easy -> beyond-ceiling hardness here.
    for noise in (0.0, 1.0, 4.0, 10.0, 20.0):
        qn = jnp.asarray(vectors.noisy_queries(b.ds.queries, noise, seed=7))
        _, gt_n = flat.search(qn, x, common.K)
        # attainability ceiling: plain search recall
        plain = darth_search.plain_search(eng, qn)
        ceil = float(np.asarray(flat.recall_at_k(eng.topk_i(plain),
                                                 gt_n)).mean())
        _, ii, st, _ = _run_darth(d, qn, r_target)
        rec_darth = float(np.asarray(flat.recall_at_k(ii, gt_n)).mean())
        inner = darth_search.budget_search(eng, qn, drt)
        rec_base = float(np.asarray(flat.recall_at_k(
            eng.topk_i(inner), gt_n)).mean())
        eng_rem = engines.ivf_engine(b.ivf_index, k=common.K,
                                     nprobe=rem.mapping[r_target])
        inner = darth_search.plain_search(eng_rem, qn)
        rec_rem = float(np.asarray(flat.recall_at_k(
            eng_rem.topk_i(inner), gt_n)).mean())
        inner = baselines.laet_search(laet, eng, qn,
                                      laet.multipliers[r_target])
        rec_laet = float(np.asarray(flat.recall_at_k(
            eng.topk_i(inner), gt_n)).mean())
        rows.append({"noise_pct": noise, "ceiling": round(ceil, 4),
                     "darth": round(rec_darth, 4),
                     "baseline": round(rec_base, 4),
                     "rem": round(rec_rem, 4), "laet": round(rec_laet, 4)})
    # robustness score: mean shortfall vs attainable min(target, ceiling)
    def shortfall(key):
        return np.mean([max(min(r_target, r["ceiling"]) - r[key], 0.0)
                        for r in rows])
    headline = (f"shortfall darth={shortfall('darth'):.3f} "
                f"baseline={shortfall('baseline'):.3f} "
                f"rem={shortfall('rem'):.3f} laet={shortfall('laet'):.3f}")
    return rows, headline


# --- Fig 18/20: OOD workloads ----------------------------------------------

def fig18_ood(r_target: float = 0.90) -> Tuple[Rows, str]:
    b = common.setup()
    d = b.darth_ivf
    eng = d.engine
    x = jnp.asarray(b.ds.base)
    q_ood = jnp.asarray(vectors.ood_queries(b.ds.base.shape[1], 512, seed=9,
                                             cluster_std=1.3))
    _, gt_o = flat.search(q_ood, x, common.K)
    plain = darth_search.plain_search(eng, q_ood)
    ceil = float(np.asarray(flat.recall_at_k(eng.topk_i(plain),
                                             gt_o)).mean())
    plain_nd = float(np.asarray(plain.ndis).mean())
    rows = []
    for rt in (0.80, 0.90, 0.95):
        _, ii, st, _ = _run_darth(d, q_ood, rt)
        rec = float(np.asarray(flat.recall_at_k(ii, gt_o)).mean())
        nd = float(np.asarray(st.inner.ndis).mean())
        rows.append({"target": rt, "recall": round(rec, 4),
                     "ceiling": round(ceil, 4),
                     "speedup_dists": round(plain_nd / max(nd, 1), 2),
                     "met": rec >= min(rt, ceil - 0.01) - 0.02})
    headline = f"OOD: all targets attainable met={all(r['met'] for r in rows)}"
    return rows, headline


# --- §4.1.4 feature ablation -------------------------------------------------

def feature_ablation() -> Tuple[Rows, str]:
    b = common.setup()
    d = b.darth_ivf
    log = d._last_log
    mask = log.valid.reshape(-1)
    xf = log.features.reshape(-1, features.NUM_FEATURES)[mask]
    y = log.recall.reshape(-1)[mask]
    rng = np.random.default_rng(0)
    sel = rng.choice(xf.shape[0], min(300_000, xf.shape[0]), replace=False)
    xf, y = xf[sel], y[sel]
    n_hold = int(0.1 * len(y))
    groups = {
        "index_only": [0, 1, 2],
        "index+nn_dist": [0, 1, 2, 3, 4, 5],
        "index+nn_stats": [0, 1, 2, 6, 7, 8, 9, 10],
        "nn_only": [3, 4, 5, 6, 7, 8, 9, 10],
        "all": list(range(features.NUM_FEATURES)),
    }
    rows = []
    for name, cols in groups.items():
        p = gbdt.fit(xf[n_hold:][:, cols], y[n_hold:],
                     gbdt.GBDTConfig(num_trees=60, depth=5))
        xh = jnp.asarray(xf[:n_hold][:, cols])
        pred = np.asarray(gbdt.predict_efficient(p, xh))
        m = regression_metrics(pred, y[:n_hold])
        rows.append({"features": name, "mse": round(m["mse"], 5),
                     "r2": round(m["r2"], 3)})
    best = min(rows, key=lambda r: r["mse"])
    headline = f"best={best['features']} mse={best['mse']}"
    return rows, headline


# --- §4.1.5 model selection ---------------------------------------------------

def model_selection() -> Tuple[Rows, str]:
    b = common.setup()
    log = b.darth_ivf._last_log
    mask = log.valid.reshape(-1)
    xf = log.features.reshape(-1, features.NUM_FEATURES)[mask]
    y = log.recall.reshape(-1)[mask]
    rng = np.random.default_rng(0)
    sel = rng.choice(xf.shape[0], min(200_000, xf.shape[0]), replace=False)
    xf, y = xf[sel], y[sel]
    n_hold = int(0.1 * len(y))
    xtr, ytr, xho, yho = xf[n_hold:], y[n_hold:], xf[:n_hold], y[:n_hold]
    rows = []
    p = gbdt.fit(xtr, ytr, gbdt.GBDTConfig(num_trees=100, depth=6))
    rows.append(("gbdt", gbdt.predict_efficient(p, jnp.asarray(xho))))
    p = gbdt.fit_random_forest(xtr[:60_000], ytr[:60_000], num_trees=40,
                               depth=6)
    rows.append(("random_forest", gbdt.predict_efficient(p, jnp.asarray(xho))))
    p = gbdt.fit_decision_tree(xtr, ytr, depth=8)
    rows.append(("decision_tree", gbdt.predict_efficient(p, jnp.asarray(xho))))
    lm = gbdt.fit_linear(xtr, ytr)
    rows.append(("linear", lm.predict(jnp.asarray(xho))))
    out = []
    for name, pred in rows:
        m = regression_metrics(np.asarray(pred), yho)
        out.append({"model": name, "mse": round(m["mse"], 5),
                    "r2": round(m["r2"], 3)})
    order = [r["model"] for r in sorted(out, key=lambda r: r["mse"])]
    headline = f"ranking={order}"
    return out, headline


# --- beyond paper: serving engine compaction ---------------------------------

def serving_compaction() -> Tuple[Rows, str]:
    from repro.serve import DarthServer
    b = common.setup()
    d = b.darth_ivf

    q = b.ds.queries
    rts = np.full((q.shape[0],), 0.9, np.float32)
    rows = []
    # no-compaction reference: fixed batches, whole batch runs to slowest
    eng = d.engine
    st = darth_search.darth_search(eng, jnp.asarray(q), 0.9,
                                   d.trained.predictor,
                                   d.interval_params(0.9))
    batch_steps = float(np.asarray(st.steps))  # steps for whole batch
    no_compact_slot_steps = batch_steps * q.shape[0]
    server = DarthServer(eng, d.trained.predictor, d.interval_for_target,
                         num_slots=64, steps_per_sync=2)
    results, stats = server.serve(q, rts)
    rows.append({"mode": "no_compaction",
                 "slot_steps_per_query": round(no_compact_slot_steps
                                               / q.shape[0], 1)})
    rows.append({"mode": "compaction",
                 "slot_steps_per_query": round(stats.slot_steps
                                               / max(stats.completed, 1), 1),
                 "completed": stats.completed, "refills": stats.refills})
    gain = no_compact_slot_steps / max(stats.slot_steps, 1)
    headline = f"compaction throughput gain={gain:.2f}x"
    return rows, headline
