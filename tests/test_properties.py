"""Property-based invariants (hypothesis):

  * collectives.merge_topk — idempotence, permutation-invariance of the
    candidate columns, and the +inf -> id -1 masking contract that keeps
    shard padding out of results.
  * ivf.build SQ8 storage — per-dim affine round-trip error is bounded
    by half a quantization step, and bucket_sqnorm matches the norms of
    the DEQUANTIZED vectors (what quantized search actually measures).
  * mutate.delta.DeltaTier — arbitrary insert/delete/wrap interleavings
    preserve the ring invariants (free-slot-only placement, tombstone
    pad convention, live-count accounting), and merging an EMPTY delta
    into a base top-k is the identity.
  * mutate.MutableIndex compaction under load — arbitrary interleavings
    of insert/delete/background-tick/swap keep the ledger coherent: the
    live set always equals a model-dict oracle, tombstones never surface
    through the serving wrapper (including deletes landing mid-rebuild),
    and the post-drain base equals the oracle exactly.
"""
import numpy as np
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.dist import collectives
from repro.index import ivf
from repro.mutate import delta as delta_lib


def _candidates(rng, b, m, inf_frac):
    d = rng.uniform(0.0, 100.0, (b, m)).astype(np.float32)
    # distinct distances -> unique top-k selection, no tie ambiguity
    d = d + np.arange(b * m, dtype=np.float32).reshape(b, m) * 1e-3
    mask = rng.random((b, m)) < inf_frac
    d = np.where(mask, np.inf, d)
    ids = np.where(mask, -1, rng.integers(0, 10_000, (b, m))).astype(np.int32)
    return jnp.asarray(d), jnp.asarray(ids)


@settings(deadline=None, max_examples=20)
@given(b=st.integers(1, 8), m=st.integers(1, 40), k=st.integers(1, 12),
       inf_frac=st.floats(0.0, 1.0))
def test_merge_topk_idempotent_and_masked(b, m, k, inf_frac):
    k = min(k, m)   # merge_topk contract: at least k candidate columns
    rng = np.random.default_rng(b * 1000 + m * 10 + k)
    cand_d, cand_i = _candidates(rng, b, m, inf_frac)
    d1, i1 = collectives.merge_topk(cand_d, cand_i, k)
    assert d1.shape == (b, k) and i1.shape == (b, k)
    d_np = np.asarray(d1)
    # ascending (inf -> finite sentinel: inf-inf diffs are nan), and +inf
    # slots report id -1 (the shard-padding contract)
    assert (np.diff(np.nan_to_num(d_np, posinf=3e38), axis=1) >= 0).all()
    assert (np.asarray(i1)[~np.isfinite(d_np)] == -1).all()
    # idempotence: merging the merged list again is a fixed point
    d2, i2 = collectives.merge_topk(d1, i1, k)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@settings(deadline=None, max_examples=20)
@given(b=st.integers(1, 6), m=st.integers(2, 40), k=st.integers(1, 10),
       seed=st.integers(0, 10_000))
def test_merge_topk_permutation_invariant(b, m, k, seed):
    k = min(k, m)
    rng = np.random.default_rng(seed)
    cand_d, cand_i = _candidates(rng, b, m, 0.2)
    perm = rng.permutation(m)
    d1, i1 = collectives.merge_topk(cand_d, cand_i, k)
    d2, i2 = collectives.merge_topk(cand_d[:, perm], cand_i[:, perm], k)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@settings(deadline=None, max_examples=10)
@given(n=st.integers(64, 400), d=st.integers(2, 24),
       scale_pow=st.floats(-2.0, 2.0), seed=st.integers(0, 1000))
def test_sq8_round_trip_error_bound(n, d, scale_pow, seed):
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(n, d)) * 10.0 ** scale_pow).astype(np.float32)
    index = ivf.build(x, nlist=4, iters=3, seed=0, quantize=True)
    assert index.quantized

    ids = np.asarray(index.bucket_ids)
    vecs = np.asarray(index.bucket_vecs).astype(np.float32)
    scale = np.asarray(index.scale)
    offset = np.asarray(index.offset)
    x_hat = vecs * scale[None, None, :] + offset[None, None, :]

    valid = ids >= 0
    err = np.abs(x_hat[valid] - x[ids[valid]])
    # affine SQ8: |x - x_hat| <= scale/2 per dim (0.51 absorbs the f32
    # rounding of the round-trip itself, which is << scale); in-range
    # data never clips because scale >= (hi - lo) / 254 maps to ±127.
    bound = 0.51 * scale[None, :]
    assert (err <= bound).all(), float((err - bound).max())

    # bucket_sqnorm is computed on the DEQUANTIZED vectors
    sqn = np.asarray(index.bucket_sqnorm)
    np.testing.assert_allclose(sqn[valid], (x_hat[valid] ** 2).sum(axis=1),
                               rtol=1e-4, atol=1e-4)
    # padding contract survives quantized builds
    assert np.isposinf(sqn[~valid]).all()
    assert (np.asarray(index.bucket_vecs)[~valid] == 0).all()


# ---------------------------------------------------------------------------
# DeltaTier ring invariants under arbitrary insert/delete interleavings
# ---------------------------------------------------------------------------

def _tiny_base(dim):
    """Smallest possible base index: the properties target the DELTA
    ring bookkeeping, so the base just anchors MutableIndex (its one
    bucket never changes)."""
    from repro.index import ivf as ivf_lib
    rng = np.random.default_rng(7)
    x = rng.normal(size=(8, dim)).astype(np.float32)
    return ivf_lib.build(x, nlist=1, iters=1, seed=0)


@settings(deadline=None, max_examples=12)
@given(capacity=st.integers(4, 40), dim=st.integers(2, 8),
       seed=st.integers(0, 100_000), nops=st.integers(1, 30))
def test_delta_tier_interleavings_preserve_invariants(capacity, dim, seed,
                                                      nops):
    """Arbitrary interleavings of insert / delete (forcing ring wraps
    through repeated fill-and-free cycles) keep the DeltaTier invariants:

      * free-slot-only placement — a live slot is never overwritten, so
        every live id still holds exactly the vector it was inserted
        with;
      * tombstoned / empty slots carry the pad convention (ids -1,
        sqnorm +inf) and live slots carry their true sqnorm;
      * live-count accounting — num_delta == inserts - deletes (into /
        of the delta), and MutableIndex.num_live == issued - deleted.
    """
    from repro.mutate import MutableIndex

    mut = MutableIndex(_tiny_base(dim), capacity=capacity)
    rng = np.random.default_rng(seed)
    model = {}            # live delta id -> its vector (the oracle)
    n_ins = n_del = 0
    for _ in range(nops):
        room = capacity - mut.num_delta
        if model and (room == 0 or rng.random() < 0.45):
            kill = rng.choice(sorted(model), size=rng.integers(
                1, len(model) + 1), replace=False)
            assert mut.delete(kill) == len(kill)
            for i in kill:
                model.pop(int(i))
            n_del += len(kill)
        elif room > 0:
            m = int(rng.integers(1, room + 1))
            vecs = rng.normal(size=(m, dim)).astype(np.float32)
            ids = mut.insert(vecs)
            assert len(ids) == m
            for j, i in enumerate(ids):
                assert int(i) not in model   # ids never reused
                model[int(i)] = vecs[j]
            n_ins += m

        d_ids = np.asarray(jax.device_get(mut.delta.ids))
        d_vecs = np.asarray(jax.device_get(mut.delta.vecs))
        d_sqn = np.asarray(jax.device_get(mut.delta.sqnorm))
        live = d_ids >= 0
        # live-count accounting
        assert mut.num_delta == n_ins - n_del == int(live.sum())
        assert set(d_ids[live].tolist()) == set(model)
        # free-slot-only placement: every live id still holds its vector
        for slot in np.nonzero(live)[0]:
            np.testing.assert_array_equal(d_vecs[slot],
                                          model[int(d_ids[slot])])
        # pad convention: dead/empty slots are +inf / -1, live carry
        # their true sqnorm
        assert np.isposinf(d_sqn[~live]).all()
        np.testing.assert_allclose(
            d_sqn[live], (d_vecs[live] ** 2).sum(axis=1), rtol=1e-5,
            atol=1e-5)
    # base ids untouched by delta churn
    assert mut.num_live == 8 + n_ins - n_del


@settings(deadline=None, max_examples=15)
@given(b=st.integers(1, 8), k=st.integers(1, 10), dim=st.integers(2, 12),
       capacity=st.integers(10, 64), inf_frac=st.floats(0.0, 0.6),
       seed=st.integers(0, 10_000))
def test_empty_delta_merge_is_identity(b, k, dim, capacity, inf_frac,
                                       seed):
    """Merging an EMPTY delta's scan into any well-formed base top-k is
    the identity — the contract that makes mutable_engine bit-for-bit
    equal to its base engine post-compaction."""
    rng = np.random.default_rng(seed)
    delta = delta_lib.make_delta(capacity, dim)
    q = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    dd, di, live, nins = delta_lib.delta_topk(delta, q, k)
    assert int(live) == 0 and (np.asarray(nins) == 0).all()
    assert np.isposinf(np.asarray(dd)).all()
    assert (np.asarray(di) == -1).all()

    # well-formed base top-k: ascending, +inf tail with ids -1
    base_d = np.sort(rng.uniform(0.0, 100.0, (b, k)).astype(np.float32), 1)
    n_inf = (rng.random((b, 1)) * (k + 1)).astype(int)
    tail = np.arange(k)[None, :] >= (k - n_inf)
    base_d = np.where(tail, np.inf, base_d)
    base_i = np.where(tail, -1,
                      rng.integers(0, 10_000, (b, k))).astype(np.int32)

    m_d, m_i = collectives.merge_topk(
        jnp.concatenate([jnp.asarray(base_d), dd], axis=1),
        jnp.concatenate([jnp.asarray(base_i), di], axis=1), k)
    np.testing.assert_array_equal(np.asarray(m_d), base_d)
    np.testing.assert_array_equal(np.asarray(m_i), base_i)


# -- overload admission control (serve.difficulty) ------------------------

_SERVE_FIXTURE = {}


def _overload_fixture():
    """One tiny served stack shared across hypothesis examples (the
    chunk jits compile once; every example only re-runs the host-side
    admission logic plus a handful of small device chunks). The stub
    predictor pins recall at 0, so no query terminates early and every
    admitted query runs exactly nprobe engine steps — admission
    decisions, not search dynamics, drive the outcome."""
    if _SERVE_FIXTURE:
        return _SERVE_FIXTURE["v"]
    from repro.core import engines
    from repro.core.intervals import IntervalParams
    from repro.data import vectors

    ds = vectors.make_dataset(n=600, d=8, num_learn=16, num_queries=96,
                              clusters=4, cluster_std=1.0, seed=5)
    index = ivf.build(ds.base, nlist=4, seed=5)
    eng = engines.ivf_engine(index, k=5, nprobe=4)

    def predictor(feats):
        return jnp.zeros((feats.shape[0],), jnp.float32)

    def interval_for_target(rt):
        rt = np.atleast_1d(rt)
        return IntervalParams(ipi=np.full(rt.shape, 8.0, np.float32),
                              mpi=np.full(rt.shape, 4.0, np.float32))

    _SERVE_FIXTURE["v"] = (ds, eng, predictor, interval_for_target)
    return _SERVE_FIXTURE["v"]


@settings(deadline=None, max_examples=10)
@given(n=st.integers(9, 96), max_queue=st.integers(0, 24),
       shed=st.booleans(), log_hosts=st.integers(0, 2),
       hard_quantile=st.floats(0.0, 1.0),
       hard_frac=st.floats(0.0, 0.5))
def test_overload_admission_never_silently_drops(n, max_queue, shed,
                                                 log_hosts, hard_quantile,
                                                 hard_frac):
    """Overload admission control: under a query stream exceeding slot
    capacity with a bounded queue, EVERY query id is accounted for —
    served (a result came back), or explicitly shed (its id recorded in
    HostStats.shed_ids, its result None). Nothing is silently dropped,
    nothing returns twice, and under overload="degrade" every query is
    served. The per-host ledger (admitted = completed + truncated,
    stripe = admitted + shed) must balance exactly."""
    from repro.serve import DarthServer, TierConfig

    ds, eng, predictor, interval_for_target = _overload_fixture()
    hosts = 2 ** log_hosts
    tiers = TierConfig(hard_quantile=hard_quantile,
                       hard_slot_fraction=hard_frac,
                       max_queue=max_queue,
                       overload="shed" if shed else "degrade",
                       degrade_target=0.5)
    server = DarthServer(eng, predictor, interval_for_target,
                         num_slots=8, steps_per_sync=2, hosts=hosts,
                         tiers=tiers)
    rts = np.full((n,), 0.9, np.float32)
    results, stats = server.serve(ds.queries[:n], rts)

    served = {i for i, r in enumerate(results) if r is not None}
    shed_ids = [q for h in stats.hosts for q in h.shed_ids]
    assert len(shed_ids) == len(set(shed_ids))          # no double-shed
    assert served.isdisjoint(shed_ids)                  # shed => no result
    assert served | set(shed_ids) == set(range(n))      # total accounting
    assert stats.shed == len(shed_ids)
    if not shed:
        assert not shed_ids and len(served) == n        # degrade serves all
        # only queue overflow beyond max_queue is degraded, never more
        assert stats.degraded <= max(n - hosts * max_queue, 0)
    for h in stats.hosts:
        assert h.admitted == h.completed + h.truncated
        stripe = len(range(h.host, n, hosts))
        assert stripe == h.admitted + h.shed + h.abandoned


# ---------------------------------------------------------------------------
# Compaction under load: insert / delete / tick / swap interleavings
# ---------------------------------------------------------------------------

def _prop_base():
    """Shared tiny IVF base for the compaction-under-load property —
    deletes/compactions REPLACE MutableIndex.base functionally, so the
    built index object is never mutated and examples can share it."""
    from repro.index import ivf as ivf_lib
    rng = np.random.default_rng(11)
    x = rng.normal(size=(96, 6)).astype(np.float32)
    return x, ivf_lib.build(x, nlist=4, iters=2, seed=0)


_PROP_X, _PROP_INDEX = _prop_base()


@settings(deadline=None, max_examples=10)
@given(ops=st.lists(st.sampled_from(["insert", "delete", "tick", "swap"]),
                    min_size=4, max_size=28),
       seed=st.integers(0, 100_000))
def test_compaction_under_load_preserves_ledger_invariants(ops, seed):
    """Arbitrary interleavings of insert / delete / background-tick /
    swap keep the mutable-index ledger coherent:

      * the live set is always exactly (issued - tombstoned) — a
        model-dict oracle over ids -> vectors, regardless of where each
        id currently lives (base, shadow-in-flight, or delta ring);
      * tombstones never surface through the serving wrapper, even for
        ids deleted WHILE their fold was being rebuilt (the
        deleted_since re-application at swap);
      * mid-rebuild inserts survive the swap live in the ring;
      * a full-probe search through mutable_engine returns the exact
        nearest neighbor of the live universe (brute-force oracle).
    """
    from repro import mutate
    from repro.core import darth_search, engines

    mut = mutate.MutableIndex(_PROP_INDEX, capacity=32)
    rng = np.random.default_rng(seed)
    model = {int(i): _PROP_X[i] for i in range(96)}
    dead = set()
    for op in ops:
        if op == "insert":
            room = 32 - mut.num_delta
            if room <= 0:
                continue
            m = int(rng.integers(1, min(room, 4) + 1))
            vecs = rng.normal(size=(m, 6)).astype(np.float32)
            for j, i in enumerate(mut.insert(vecs)):
                model[int(i)] = vecs[j]
        elif op == "delete":
            if not model:
                continue
            kill = rng.choice(sorted(model), size=min(3, len(model)),
                              replace=False)
            assert mut.delete(kill) == len(kill)
            for i in kill:
                model.pop(int(i))
                dead.add(int(i))
        elif op == "tick":
            if not mut.compacting:
                mut.begin_compaction()
            else:
                mut.compact_tick()
        elif op == "swap":
            if mut.compacting and mut._job.done:
                mut.swap_compaction()
        # ledger: live set == oracle, tombstones out, delta counted
        assert mut.num_live == len(model)
        live_ids, live_vecs = mut.live_vectors()
        assert set(int(i) for i in live_ids) == set(model)
        assert not (set(int(i) for i in live_ids) & dead)
        order = np.argsort(live_ids)
        np.testing.assert_array_equal(
            live_vecs[order],
            np.stack([model[int(i)] for i in np.sort(live_ids)]))

    # drain: finish any in-flight rebuild, then fold the leftovers —
    # the end state must equal the oracle exactly
    if mut.compacting:
        while not mut.compact_tick():
            pass
        mut.swap_compaction()
    if mut.num_delta or len(model) != np.count_nonzero(
            np.asarray(mut.base.bucket_ids) >= 0):
        mut.compact()
    bi = np.asarray(mut.base.bucket_ids)
    assert set(bi[bi >= 0].tolist()) == set(model)
    assert mut.num_delta == 0

    if model:
        meng = engines.mutable_engine(
            engines.ivf_engine(mut.base, k=1, nprobe=4), mut.delta)
        probe_id = sorted(model)[int(rng.integers(0, len(model)))]
        ws = darth_search.plain_search(
            meng, jnp.asarray(model[probe_id][None, :]))
        assert int(np.asarray(meng.topk_i(ws))[0, 0]) == probe_id
        assert not (dead
                    & set(np.asarray(meng.topk_i(ws)).ravel().tolist()))
