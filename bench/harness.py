"""One run of one benchmark cell: set-up, measured window, check, result.

Everything a cell is made of is found by name:

  BENCHMARK.json                 the cells, metrics and their bounds
  bench/configs/<config>.json    the deployment: shapes, index, serving,
                                 data, fit, guarantee and check limits
  bench/references/<ref>.py      its plain reference (`reference` key)
  bench/traffic/<mix>.json       the traffic mix (see bench/traffic.py)
  bench/metrics/<metric>.py      `read(run)`: one metric from the run's
                                 records, or None where it has nothing

so a later cell, mix or metric is added by adding files and entries.

The system under test is the program's one-chip serving path: IVF build
(`repro.index.ivf.build`), `repro.core.api.Darth.fit` over the engine the
configuration names, and `repro.serve.DarthServer.serve`. The collection,
learn set, query pool, index and predictor are made from the run's seed.
A configuration's `metric`, `index.kind`, `index.store` and
`serving.engine` are read and checked against the tables below
(`served_as`): a deployment the harness does not serve is refused, and
serving a new one (a sharded engine, a re-ranked store, a graph index)
takes code here.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import check as check_lib  # noqa: E402
from bench import data as data_lib  # noqa: E402
from bench import trace_reduce  # noqa: E402
from bench import traffic as traffic_lib  # noqa: E402

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell needs."""


@dataclasses.dataclass
class Call:
    """One serve call of the window (host clock, seconds from its start)."""
    t0: float
    t1: float
    queries: int
    completed: int
    truncated: int
    engine_steps: int
    slot_steps: int
    ndis: int
    chunk_ms_sum: Optional[float]   # traced runs: the server's chunk times
    exhausted: Optional[int] = None  # traced runs: queries that probed
    #                                  every list (held their slot nprobe
    #                                  engine steps or more)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: str
    config: dict
    mix: dict
    device_kind: str
    setup_s: float
    setup_parts: Dict[str, float]
    calls: List[Call]
    window_s: float
    latencies_ms: Optional[np.ndarray]   # open loop: per due query
    trace: Optional[dict]                # trace_reduce.reduce output


# -- finding things by name -------------------------------------------------

def load_spec(path: Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: dict, workload: str):
    """(cell entry, configuration, traffic mix) of a workload name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = _json(ROOT / conf["file"])
    mix = _json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell, cfg, mix


def metrics_for(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with trace its per-layer ones."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py").read


def reference(cfg: dict):
    return load_module(BENCH / "references" / f"{cfg['reference']}.py")


# -- set-up -----------------------------------------------------------------

def require_chips(chips: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found {info['platform']} "
                     f"({info['kind']})")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{info['count']}")
    return info


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), every program cached."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


@contextlib.contextmanager
def timed(parts: Dict[str, float], name: str):
    t0 = time.perf_counter()
    yield
    parts[name] = time.perf_counter() - t0


def serve_budget(cfg: dict, n: int) -> int:
    """Engine steps no sound serve call of n queries reaches. A query
    holds its slot for at most nprobe steps plus part of a chunk, and the
    slots take queued queries as they free: the last query finishes within
    (n / slots + 1) such holds (list scheduling's bound)."""
    s = cfg["serving"]
    waves = math.ceil(n / s["num_slots"]) + 1
    return waves * (cfg["index"]["nprobe"] + s["steps_per_sync"])


# What a configuration may state, and how the harness serves it. A value
# outside these tables is refused before anything is built.
METRICS = ("squared_l2",)
INDEX_KINDS = ("ivf_flat",)
STORES = {"float32": False, "int8": True}      # store -> ivf.build quantize
ENGINES = ("ivf_engine",)                      # repro.core.engines, unsharded


def served_as(cfg: dict):
    """(quantize, engine factory) of the deployment `cfg` states."""
    icfg, scfg = cfg["index"], cfg["serving"]
    for what, value, known in (("metric", cfg["metric"], METRICS),
                               ("index kind", icfg["kind"], INDEX_KINDS),
                               ("store", icfg["store"], tuple(STORES)),
                               ("engine", scfg["engine"], ENGINES)):
        if value not in known:
            raise ValueError(f"{cfg['name']}: {what} {value!r} is not one "
                             f"the harness serves ({', '.join(known)})")
    from repro.core import engines
    return STORES[icfg["store"]], getattr(engines, scfg["engine"])


def with_store(cfg: dict, store: str) -> dict:
    """`cfg` with another bucket store (the control's lower precision)."""
    cfg = copy.deepcopy(cfg)
    cfg["index"]["store"] = store
    return cfg


def build_system(cfg: dict, seed: int, parts: Dict[str, float], targets, *,
                 metrics=None):
    """Data, IVF index, DARTH fit and a warmed-up server, all from the
    run's seed, served as the configuration states. Returns (server, base
    on the host, query pool on the host)."""
    quantize, make_engine = served_as(cfg)
    from repro.core import api
    from repro.index import ivf
    from repro.serve import DarthServer

    with timed(parts, "data_s"):
        col = data_lib.generate(cfg, seed)
        base = np.asarray(col.base)
        pool, learn = np.asarray(col.pool), col.learn
        del col
    icfg = cfg["index"]
    with timed(parts, "build_s"):
        index = ivf.build(base, icfg["nlist"], seed=seed % (2 ** 31),
                          cap_round=icfg["cap"], quantize=quantize)
        jax.block_until_ready(index.bucket_vecs)
    largest = int(np.asarray(index.bucket_sizes).max())
    print(f"[setup] IVF nlist {index.nlist}, cap {index.cap} (configured "
          f"{icfg['cap']}, largest bucket {largest}), store "
          f"{index.bucket_vecs.dtype}, engine {cfg['serving']['engine']}",
          flush=True)

    def make(**kw):
        return make_engine(index, **kw)
    darth = api.Darth(make_engine=make,
                      engine=make(k=cfg["k"], nprobe=icfg["nprobe"]))
    fcfg = cfg["fit"]
    with timed(parts, "fit_total_s"):
        # the collection goes in from the host: the fit's ground truth
        # moves it to the device for that call only, so the observation
        # scan that follows has the memory the bucket store leaves
        trained = darth.fit(learn, base, max_samples=fcfg["max_samples"],
                            seed=seed % (2 ** 31))
    parts["fit_s"] = trained.train_seconds
    parts["observe_s"] = parts.pop("fit_total_s") - trained.train_seconds
    scfg = cfg["serving"]
    server = DarthServer(darth.engine, trained.predictor,
                         darth.interval_for_target,
                         num_slots=scfg["num_slots"],
                         steps_per_sync=scfg["steps_per_sync"],
                         metrics=metrics)
    with timed(parts, "warmup_s"):
        # two slot pools: the first fill and the refill path
        warm = np.resize(np.asarray(learn),
                         (2 * scfg["num_slots"], cfg["dim"]))
        rt = np.resize(np.asarray(targets, np.float32),
                       warm.shape[0])
        server.serve(warm, rt, max_engine_steps=serve_budget(
            cfg, warm.shape[0]))
    return server, base, pool


# -- the measured window ----------------------------------------------------

def _samples(metrics, name: str) -> List[float]:
    """Raw samples of one of the server's histograms (traced runs)."""
    return metrics.histogram(name).samples.get((), [])


def _serve(server, cfg, q, targets, metrics, annotate, t_start):
    if metrics is not None:
        n_chunks = len(_samples(metrics, "darth_chunk_latency_ms"))
        n_served = len(_samples(metrics, "darth_service_steps"))
    with annotate("serve_call"):
        t0 = time.perf_counter()
        results, stats = server.serve(
            q, targets, max_engine_steps=serve_budget(cfg, q.shape[0]))
        t1 = time.perf_counter()
    call = Call(t0 - t_start, t1 - t_start, q.shape[0], stats.completed,
                stats.truncated, stats.engine_steps, stats.slot_steps,
                stats.ndis_harvested, None)
    if metrics is not None:
        # a query's service steps run from its admission to the chunk
        # boundary that harvests it: all nprobe probes read >= nprobe
        steps = _samples(metrics, "darth_service_steps")[n_served:]
        call.chunk_ms_sum = float(sum(
            _samples(metrics, "darth_chunk_latency_ms")[n_chunks:]))
        call.exhausted = sum(s >= cfg["index"]["nprobe"] for s in steps)
    return results, call


def closed_window(server, cfg, mix, seed, seconds, pool, metrics, annotate):
    """Back-to-back calls; the window ends with the return of the last
    call started before `seconds`."""
    calls, answers = [], []
    t_start = time.perf_counter()
    c = 0
    while time.perf_counter() - t_start < seconds:
        idx, targets = traffic_lib.closed_call(mix, seed, c, pool.shape[0])
        results, call = _serve(server, cfg, pool[idx], targets, metrics,
                               annotate, t_start)
        with annotate("collect"):
            calls.append(call)
            answers.append(check_lib.collect(idx, targets, results,
                                             call.truncated, cfg["k"]))
        c += 1
    return calls, answers, calls[-1].t1, None


def open_window(server, cfg, plan, pool, metrics, annotate):
    """Arrivals on the plan's clock: each call takes every query due by
    the time it starts; with none due the harness waits for the next.
    Latency runs from a query's due time to its call's return."""
    due = plan.due_s
    lat = np.full(due.shape, np.nan)
    calls, answers, late = [], [], []
    t_start = time.perf_counter()
    nxt = 0
    while nxt < due.shape[0]:
        now = time.perf_counter() - t_start
        if due[nxt] > now:
            with annotate("wait_due"):
                time.sleep(due[nxt] - now)
            now = time.perf_counter() - t_start
            late.append(now - due[nxt])
        hi = int(np.searchsorted(due, now, side="right"))
        sel = np.arange(nxt, hi)
        idx, targets = plan.query_ids[sel], plan.targets[sel]
        results, call = _serve(server, cfg, pool[idx], targets, metrics,
                               annotate, t_start)
        with annotate("collect"):
            lat[sel] = (call.t1 - due[sel]) * 1e3
            calls.append(call)
            answers.append(check_lib.collect(idx, targets, results,
                                             call.truncated, cfg["k"]))
        nxt = hi
    if late:
        print(f"[window] dispatch after an idle wait ran late by mean "
              f"{np.mean(late) * 1e3:.3f} ms, max {np.max(late) * 1e3:.3f}"
              f" ms over {len(late)} waits", flush=True)
    return calls, answers, calls[-1].t1, lat


# -- one run ----------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, spec: Optional[dict] = None,
             rehearsal: bool = False, adjust=None) -> int:
    """One run. Prints set-up, window and check lines, the checks last on
    stderr, and (unless rehearsing) the result line last on stdout.
    Returns the exit code. `adjust(cfg, mix)` returns the configuration
    and mix to run in their place: a rehearsal's small sizes, or the
    control's lower precision."""
    spec = spec or load_spec()
    cell, cfg, mix = resolve(spec, workload)
    if adjust is not None:
        cfg, mix = adjust(cfg, mix)
    if rehearsal:
        dev = jax.devices()[0]
        info = {"platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices())}
    else:
        try:
            info = require_chips(cell["chips"])
        except NoChip as e:
            print(f"[device] {e}: no result", file=sys.stderr, flush=True)
            return 2
        print(f"[setup] compile cache {enable_compile_cache()}", flush=True)
    lowered = [0]

    def count(event: str, duration_secs: float, **_) -> None:
        if event == LOWERING_EVENT:
            lowered[0] += 1
    jax.monitoring.register_event_duration_secs_listener(count)

    parts: Dict[str, float] = {}
    metrics = None
    if trace:
        from repro.obs import metrics as obs_metrics
        metrics = obs_metrics.serve_metrics(obs_metrics.MetricsRegistry())
    server, base, pool = build_system(cfg, seed, parts, mix["targets"],
                                      metrics=metrics)
    plan = traffic_lib.plan(mix, seed, seconds, pool.shape[0])
    setup_s = time.perf_counter() - t_start
    print("[setup] " + ", ".join(f"{k} {v:.3f}" for k, v in parts.items())
          + f"; setup_s {setup_s:.3f}", flush=True)

    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    lowered_before = lowered[0]
    with annotate(trace_reduce.WINDOW_SPAN):
        if plan.loop == "closed":
            calls, answers, window_s, lat = closed_window(
                server, cfg, mix, seed, seconds, pool, metrics, annotate)
        else:
            calls, answers, window_s, lat = open_window(
                server, cfg, plan, pool, metrics, annotate)
    in_window = lowered[0] - lowered_before
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce_dir(str(TRACE_DIR))
    print(f"[window] {len(calls)} serve calls, {window_s:.3f} s, programs "
          f"lowered in the window {in_window}; calls (queries, s, engine "
          f"steps): " + " ".join(f"{c.queries},{c.t1 - c.t0:.3f},"
                                 f"{c.engine_steps}" for c in calls),
          flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    info["memory_peak_bytes"] = stats.get("peak_bytes_in_use")

    # the program's state goes before the reference runs
    del server
    gc.collect()
    ans = check_lib.concat(answers)
    t_check = time.perf_counter()
    checks = check_lib.compare(ans, pool, base, reference(cfg), cfg,
                               mix["targets"])
    print(f"[check] {ans.answered.size} answers against the reference in "
          f"{time.perf_counter() - t_check:.3f} s", flush=True)
    correct = all(check_lib.holds(c) for c in checks.values())

    run = Run(workload, cfg, mix, info["kind"], setup_s, parts, calls,
              window_s, lat, reduced)
    values = {}
    wanted = metrics_for(spec, workload, trace)
    if rehearsal:    # every reader of the cell runs
        wanted = metrics_for(spec, workload, not trace) + wanted
    for m in wanted:
        v = reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    for name, c in checks.items():
        bound = (f"<= {c['max']}" if "max" in c else f">= {c['min']}")
        print(f"[check] {name} {c['value']} (limit {bound})",
              file=sys.stderr, flush=True)
    attempted = int(ans.answered.size)
    failed = int((~ans.answered).sum())
    if rehearsal:
        print(f"[rehearsal] {workload} on {info['platform']}: correct "
              f"{correct}, attempted {attempted}, failed {failed}; metric "
              f"readers with a value: {sorted(values) or 'none'} (values "
              f"withheld: not a chip run)", flush=True)
        return 0 if correct else 1
    if trace:
        if reduced is None:
            print("[trace] no device operation in the window",
                  file=sys.stderr, flush=True)
            return 1
        info["busy_s"] = reduced["busy_s"]
        info["window_s"] = reduced["window_s"]
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": values, "device": info}
    if trace:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0
