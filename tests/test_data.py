import numpy as np
import jax.numpy as jnp

from repro.data import vectors


def test_dataset_splits_disjoint_shapes():
    ds = vectors.make_dataset(n=1000, d=8, num_learn=100, num_queries=50,
                              clusters=10, seed=0)
    assert ds.base.shape == (1000, 8)
    assert ds.learn.shape == (100, 8)
    assert ds.queries.shape == (50, 8)
    assert ds.base.dtype == np.float32


def test_noisy_queries_scale_with_pct():
    ds = vectors.make_dataset(n=500, d=16, num_learn=10, num_queries=100,
                              clusters=5, seed=1)
    q1 = vectors.noisy_queries(ds.queries, 0.05, seed=0)
    q2 = vectors.noisy_queries(ds.queries, 0.30, seed=0)
    d1 = np.linalg.norm(q1 - ds.queries, axis=1).mean()
    d2 = np.linalg.norm(q2 - ds.queries, axis=1).mean()
    assert d2 > d1 > 0


def test_noisy_queries_increase_hardness():
    """The paper's hardness definition: computational effort (distance
    calcs) required to reach a recall target grows with query noise."""
    import jax.numpy as jnp
    from repro.index import flat, ivf
    from repro.core import engines, intervals, training
    ds = vectors.make_dataset(n=6000, d=16, num_learn=10, num_queries=64,
                              clusters=48, cluster_std=2.0, seed=3)
    index = ivf.build(ds.base, nlist=48, seed=3)
    eng = engines.ivf_engine(index, k=10, nprobe=48)

    def effort(queries):
        q = jnp.asarray(queries)
        _, gt = flat.search(q, jnp.asarray(ds.base), 10)
        log = training.generate_observations(eng, q, gt, batch=64)
        return float(np.mean(intervals.dists_to_target(
            log.recall, log.ndis, log.valid, 0.99)))

    base = effort(ds.queries)
    noisy = effort(vectors.noisy_queries(ds.queries, 8.0, seed=1))
    ood = effort(vectors.ood_queries(16, 64, seed=2))
    assert noisy > base, (base, noisy)
    assert ood > noisy, (noisy, ood)


def test_ood_queries_far_from_base():
    ds = vectors.make_dataset(n=500, d=16, num_learn=10, num_queries=50,
                              clusters=5, seed=1)
    ood = vectors.ood_queries(16, 50, seed=2)
    # mean NN distance of OOD queries exceeds in-distribution queries'
    def mean_nn(qs):
        d = ((qs[:, None, :] - ds.base[None]) ** 2).sum(-1)
        return np.sqrt(d.min(1)).mean()
    assert mean_nn(ood) > mean_nn(ds.queries)


def test_lid_estimator():
    rng = np.random.default_rng(0)
    # higher-dimensional data -> higher LID
    def lid_of(d):
        x = rng.normal(size=(2000, d)).astype(np.float32)
        q = rng.normal(size=(50, d)).astype(np.float32)
        from repro.index import flat
        dists, _ = flat.search(jnp.asarray(q), jnp.asarray(x), 20)
        return float(np.median(vectors.local_intrinsic_dimensionality(
            np.asarray(dists))))
    assert lid_of(32) > lid_of(4)


def test_make_dataset_intrinsic_dim_is_low_rank():
    """intrinsic_dim=r lifts an r-dim mixture into d dims: r singular
    directions carry the spread, the rest only the small lift noise."""
    ds = vectors.make_dataset(n=2000, d=64, num_learn=100, num_queries=50,
                              clusters=16, center_scale=1.0,
                              intrinsic_dim=8, seed=0)
    assert ds.base.shape == (2000, 64) and ds.learn.shape == (100, 64)
    assert ds.queries.shape == (50, 64) and ds.base.dtype == np.float32
    x = np.concatenate([ds.base, ds.queries])
    sv = np.linalg.svd(x - x.mean(0), compute_uv=False)
    assert sv[8] < 0.2 * sv[7]
