"""Pallas kernel validation: interpret-mode shape/dtype sweeps against the
pure-jnp oracles in kernels/ref.py (deliverable c)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro import gbdt
from repro.kernels import ops, ref


@pytest.mark.parametrize("b,n,d,k", [
    (4, 257, 16, 5),
    (16, 1024, 64, 10),
    (3, 96, 7, 8),
    (128, 2048, 128, 50),
    (1, 8, 4, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_topk_matches_oracle(b, n, d, k, dtype):
    rng = np.random.default_rng(hash((b, n, d, k)) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, d)), dtype)
    x = jnp.asarray(rng.normal(size=(n, d)), dtype)
    d_k, i_k = ops.l2_topk(q, x, k=k)
    d_r, i_r = ref.l2_topk_ref(q, x, k)
    atol = 1e-3 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), atol=atol)
    if dtype == jnp.float32:
        overlap = np.mean([
            len(set(np.asarray(i_k)[i]) & set(np.asarray(i_r)[i])) / k
            for i in range(b)])
        assert overlap > 0.99


@settings(deadline=None, max_examples=12)
@given(b=st.integers(1, 40), n=st.integers(8, 600), d=st.integers(2, 48),
       k=st.integers(1, 8))
def test_l2_topk_property(b, n, d, k):
    k = min(k, n)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    d_k, i_k = ops.l2_topk(q, x, k=k)
    d_np = np.asarray(d_k)
    # invariants: ascending, non-negative, ids valid & unique per row
    assert (np.diff(d_np, axis=1) >= -1e-5).all()
    assert (d_np >= 0).all()
    ids = np.asarray(i_k)
    for row in ids:
        valid = row[row >= 0]
        assert len(set(valid.tolist())) == len(valid)
        assert (valid < n).all()


@pytest.mark.parametrize("n_feat,depth,trees,b", [
    (11, 4, 20, 37),
    (11, 6, 50, 128),
    (5, 3, 7, 9),
])
def test_gbdt_kernel_matches_oracle(n_feat, depth, trees, b):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(3000, n_feat)).astype(np.float32)
    y = (np.sin(x[:, 0]) + x[:, 1] * 0.3).astype(np.float32)
    p = gbdt.fit(x, y, gbdt.GBDTConfig(num_trees=trees, depth=depth))
    xq = jnp.asarray(rng.normal(size=(b, n_feat)).astype(np.float32))
    out_k = ops.gbdt_predict(p, xq)
    out_r = ref.gbdt_predict_ref(p, xq)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-5)


def test_gbdt_kernel_vs_xla_path():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2000, 11)).astype(np.float32)
    y = x[:, 0].astype(np.float32)
    p = gbdt.fit(x, y, gbdt.GBDTConfig(num_trees=10, depth=4))
    xq = jnp.asarray(rng.normal(size=(16, 11)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(ops.gbdt_predict(p, xq)),
        np.asarray(gbdt.predict_efficient(p, xq)), atol=1e-5)


@pytest.mark.parametrize("b,c,d,k", [
    (5, 64, 16, 7),
    (16, 128, 32, 10),
    (3, 40, 8, 5),
    (1, 8, 4, 3),
])
def test_bucket_topk_matches_oracle(b, c, d, k):
    rng = np.random.default_rng(hash((b, c, d, k)) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    vecs = jnp.asarray(rng.normal(size=(b, c, d)), jnp.float32)
    sqn = jnp.sum(vecs**2, axis=2)
    ids = jnp.asarray(rng.integers(0, 10_000, (b, c)), jnp.int32)
    ids = jnp.where(jnp.asarray(rng.random((b, c))) < 0.1, -1, ids)
    run_d = jnp.sort(jnp.asarray(rng.random((b, k)) * 20, jnp.float32), 1)
    run_i = jnp.asarray(rng.integers(0, 10_000, (b, k)), jnp.int32)
    dk_, ik_ = ops.bucket_topk(q, vecs, sqn, ids, run_d, run_i)
    dr, ir = ref.bucket_topk_ref(q, vecs, sqn, ids, run_d, run_i)
    np.testing.assert_allclose(np.asarray(dk_), np.asarray(dr), atol=1e-3)
    # output stays sorted ascending and never worse than the old top-k
    out = np.asarray(dk_)
    assert (np.diff(out, axis=1) >= -1e-5).all()
    assert (out <= np.asarray(run_d) + 1e-5).all()


@settings(deadline=None, max_examples=10)
@given(b=st.integers(1, 12), c=st.integers(4, 128), d=st.integers(2, 24),
       k=st.integers(1, 8))
def test_bucket_topk_property(b, c, d, k):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    vecs = jnp.asarray(rng.normal(size=(b, c, d)), jnp.float32)
    sqn = jnp.sum(vecs**2, axis=2)
    ids = jnp.asarray(rng.integers(0, 1000, (b, c)), jnp.int32)
    run_d = jnp.full((b, k), jnp.inf, jnp.float32)
    run_i = jnp.full((b, k), -1, jnp.int32)
    dk_, ik_ = ops.bucket_topk(q, vecs, sqn, ids, run_d, run_i)
    dr, ir = ref.bucket_topk_ref(q, vecs, sqn, ids, run_d, run_i)
    np.testing.assert_allclose(np.asarray(dk_), np.asarray(dr), atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_bucket_probe_tiled_matches_untiled(dtype):
    """Scanning a bucket in C tiles (running top-k and insert count kept
    across tiles) gives exactly the one-tile result, ties included."""
    rng = np.random.default_rng(7)
    b, c, d, k = 6, 1100, 16, 10
    if dtype == jnp.int8:
        raw = rng.integers(-127, 128, (b, c, d))
    else:
        raw = rng.normal(size=(b, c, d))
    # q sits on column 3 and column 700 repeats it: two zero distances,
    # one on each side of a tile edge, whose order must survive tiling
    raw[:, 700] = raw[:, 3]
    q = jnp.asarray(raw[:, 3], jnp.float32)
    vecs = jnp.asarray(raw, dtype)
    sqn = jnp.sum(vecs.astype(jnp.float32) ** 2, axis=2)
    ids = rng.integers(0, 10**6, (b, c))
    ids[rng.random((b, c)) < 0.1] = -1
    ids[:, [3, 700]] = [11, 22]
    ids = jnp.asarray(ids, jnp.int32)
    bias = jnp.sum(q ** 2, axis=1, keepdims=True)
    run_d = jnp.sort(jnp.asarray(rng.random((b, k)) * 50.0, jnp.float32), 1)
    run_i = jnp.asarray(rng.integers(0, 10**6, (b, k)), jnp.int32)
    kth = run_d[:, -1:]
    args = (q, vecs, sqn, ids, bias, kth, run_d, run_i)
    tiled = ops.bucket_probe(*args, bc=256)      # 5 tiles, last one padded
    whole = ops.bucket_probe(*args, bc=2048)     # one tile of all 1100
    for got, want in zip(tiled, whole):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("c,bc,tile", [
    (390, 512, 390),      # fits one tile: the whole bucket, no padding
    (2048, 512, 512),     # divides evenly
    (1560, 512, 512),     # 4 tiles of 390 each need 512 lanes
    (1100, 512, 384),     # 3 tiles: 1152 columns, not 3 x 512 = 1536
    (1100, 256, 256),     # 5 tiles of 220 each need 256 lanes
])
def test_bucket_tile_width(c, bc, tile):
    assert ops.bucket_tile(c, bc) == tile
