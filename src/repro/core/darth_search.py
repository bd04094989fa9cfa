"""DARTH early-termination search driver (paper Algorithm 1, batched).

The driver wraps any `Engine` (IVF probe loop / HNSW beam loop) and runs it
under `lax.while_loop` with:

  * per-query `idis` counters (distance calcs since last predictor call),
  * per-query adaptive prediction intervals `pi` (Eq. 1),
  * batched GBDT recall prediction, fired only when >= 1 query is due
    (`lax.cond` skips the predictor entirely otherwise),
  * per-query early termination: predicted recall >= declared target.

TPU adaptation notes (DESIGN.md §2): termination granularity is one engine
step (a bucket probe / beam expansion) rather than a single distance calc;
per-query targets are a vector, so one batch can mix declared recalls.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp

from repro.core import engines as engines_lib
from repro.core import features as features_lib
from repro.core.intervals import IntervalParams, next_interval

PredictorFn = Callable[[jax.Array], jax.Array]  # f32[B,11] -> f32[B]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DarthState:
    inner: Any
    idis: jax.Array      # i32[B] distance calcs since last predictor call
    pi: jax.Array        # f32[B] current prediction interval
    r_pred: jax.Array    # f32[B] last predicted recall (-1 = never called)
    npred: jax.Array     # i32[B] #predictor invocations
    early: jax.Array     # bool[B] terminated by DARTH (vs natural/budget)
    steps: jax.Array     # i32[] loop steps executed
    nbatch: jax.Array    # i32[] steps that ran the batched predictor


def _features(engine: engines_lib.Engine, inner: Any) -> jax.Array:
    return features_lib.extract(
        engine.nstep(inner), inner.ndis, inner.ninserts, inner.first_nn,
        engine.topk_d(inner))


def init_darth_state(engine: engines_lib.Engine, q: jax.Array,
                     params: IntervalParams) -> DarthState:
    b = q.shape[0]
    return DarthState(
        inner=engine.init(engine.index, q),
        idis=jnp.zeros((b,), jnp.int32),
        pi=jnp.broadcast_to(jnp.asarray(params.ipi, jnp.float32), (b,)),
        r_pred=jnp.full((b,), -1.0, jnp.float32),
        npred=jnp.zeros((b,), jnp.int32),
        early=jnp.zeros((b,), bool),
        steps=jnp.zeros((), jnp.int32),
        nbatch=jnp.zeros((), jnp.int32),
    )


def make_darth_body(engine: engines_lib.Engine, predictor: PredictorFn,
                    params: IntervalParams, r_t: jax.Array):
    """One Algorithm-1 iteration as a reusable jittable body (the serving
    engine drives this directly; darth_search wraps it in a while_loop).
    Its ops carry the named scopes `darth.probe` (the engine step) and
    `darth.predict` (features, predictor and interval update) in their
    metadata, so a device trace attributes them."""
    def body(st: DarthState) -> DarthState:
        prev_ndis = st.inner.ndis
        with jax.named_scope("darth.probe"):
            inner = engine.step(engine.index, st.inner)
        idis = st.idis + (inner.ndis - prev_ndis)
        due = inner.active & (idis.astype(jnp.float32) >= st.pi)
        fire = due.any()

        def with_pred(args):
            inner, idis, st_pi, st_rp, st_npred, st_early = args
            with jax.named_scope("darth.predict"):
                feats = _features(engine, inner)
                rp = jnp.clip(predictor(feats), 0.0, 1.0)
                rp = jnp.where(due, rp, st_rp)
                stop = due & (rp >= r_t)
                new_inner = engines_lib.set_active(inner,
                                                   inner.active & ~stop)
                pi = jnp.where(due, next_interval(params, r_t, rp), st_pi)
                idis2 = jnp.where(due, 0, idis)
                return (new_inner, idis2, pi, rp,
                        st_npred + due.astype(jnp.int32), st_early | stop)

        def without_pred(args):
            inner, idis, st_pi, st_rp, st_npred, st_early = args
            return (inner, idis, st_pi, st_rp, st_npred, st_early)

        inner, idis, pi, rp, npred, early = jax.lax.cond(
            fire, with_pred, without_pred,
            (inner, idis, st.pi, st.r_pred, st.npred, st.early))
        return DarthState(inner=inner, idis=idis, pi=pi, r_pred=rp,
                          npred=npred, early=early, steps=st.steps + 1,
                          nbatch=st.nbatch + fire.astype(jnp.int32))

    return body


def darth_search(engine: engines_lib.Engine, q: jax.Array,
                 r_target: Union[float, jax.Array],
                 predictor: PredictorFn,
                 params: IntervalParams) -> DarthState:
    """Run declarative-recall search to completion. Returns final state."""
    b = q.shape[0]
    r_t = jnp.broadcast_to(jnp.asarray(r_target, jnp.float32), (b,))
    st0 = init_darth_state(engine, q, params)
    body = make_darth_body(engine, predictor, params, r_t)

    def cond(st: DarthState):
        return st.inner.active.any() & (st.steps < engine.max_steps)

    return jax.lax.while_loop(cond, body, st0)


def plain_search(engine: engines_lib.Engine, q: jax.Array) -> Any:
    """Run the engine to natural termination (no early termination)."""
    inner0 = engine.init(engine.index, q)

    def cond(carry):
        inner, t = carry
        return inner.active.any() & (t < engine.max_steps)

    def body(carry):
        inner, t = carry
        return engine.step(engine.index, inner), t + 1

    inner, _ = jax.lax.while_loop(cond, body,
                                  (inner0, jnp.zeros((), jnp.int32)))
    return inner


def budget_search(engine: engines_lib.Engine, q: jax.Array,
                  budget: Union[float, jax.Array]) -> Any:
    """Fixed distance-calculation budget per query (the paper's 'Baseline'
    competitor §3.2.2 and LAET's termination primitive)."""
    b = q.shape[0]
    budget = jnp.broadcast_to(jnp.asarray(budget, jnp.float32), (b,))
    inner0 = engine.init(engine.index, q)

    def cond(carry):
        inner, t = carry
        return inner.active.any() & (t < engine.max_steps)

    def body(carry):
        inner, t = carry
        inner = engine.step(engine.index, inner)
        over = inner.ndis.astype(jnp.float32) >= budget
        inner = engines_lib.set_active(inner, inner.active & ~over)
        return inner, t + 1

    inner, _ = jax.lax.while_loop(cond, body,
                                  (inner0, jnp.zeros((), jnp.int32)))
    return inner
