import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import gbdt


def _toy(n=5000, f=11, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (np.sin(x[:, 0]) + 0.5 * (x[:, 1] > 0.3) * x[:, 2]
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    return x, y


def test_gbdt_fits_nonlinear_target():
    x, y = _toy()
    p = gbdt.fit(x, y, gbdt.GBDTConfig(num_trees=40, depth=5))
    pred = np.asarray(gbdt.predict_efficient(p, jnp.asarray(x)))
    mse = float(np.mean((pred - y) ** 2))
    assert mse < 0.05, mse          # noise floor ~0.01, var(y) ~0.5


def test_gbdt_deterministic():
    x, y = _toy(2000)
    p1 = gbdt.fit(x, y, gbdt.GBDTConfig(num_trees=10, depth=4))
    p2 = gbdt.fit(x, y, gbdt.GBDTConfig(num_trees=10, depth=4))
    np.testing.assert_array_equal(np.asarray(p1.leaf), np.asarray(p2.leaf))
    np.testing.assert_array_equal(np.asarray(p1.feat), np.asarray(p2.feat))


def test_model_selection_ordering():
    """Paper §4.1.5: GBDT <= RF < linear on nonlinear targets."""
    x, y = _toy(4000)
    g = gbdt.fit(x, y, gbdt.GBDTConfig(num_trees=40, depth=5))
    lin = gbdt.fit_linear(x, y)
    pred_g = np.asarray(gbdt.predict_efficient(g, jnp.asarray(x)))
    mse_g = float(np.mean((pred_g - y) ** 2))
    mse_l = float(np.mean((np.asarray(lin.predict(jnp.asarray(x))) - y) ** 2))
    assert mse_g < mse_l


@pytest.mark.parametrize("trees,depth,batch,n_fit", [
    (15, 4, 64, 2000),
    (100, 6, 256, 2000),    # the served shape: 256 slots
    (100, 6, 1, 2000),
    (100, 6, 3, 2000),
    (100, 6, 2500, 2000),   # more than one pass of rows
    (100, 6, 256, 300),     # few samples: degenerate nodes
])
def test_predict_paths_agree(trees, depth, batch, n_fit):
    x, y = _toy(n_fit)
    p = gbdt.fit(x, y, gbdt.GBDTConfig(num_trees=trees, depth=depth))
    if n_fit < 1000:
        feat = np.asarray(p.feat)
        assert (feat < 0).any()
        assert np.isinf(np.asarray(p.thresh)[feat < 0]).all()
    xq = jnp.asarray(_toy(batch, seed=1)[0])
    a = np.asarray(gbdt.predict(p, xq))
    b = np.asarray(gbdt.predict_efficient(p, xq))
    assert b.shape == (batch,)
    assert not np.isnan(b).any()
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def test_predict_efficient_lowers_without_gather():
    """The served shape (256 slots x 11 features, 100 trees of depth 6)
    descends by compare-and-select: a gather here is the slow form."""
    p = gbdt.empty_params(num_trees=100, depth=6)
    x = jnp.zeros((256, 11), jnp.float32)
    text = jax.jit(gbdt.predict_efficient).lower(p, x).as_text()
    assert "gather" not in text
    assert "dot_general" not in text


def test_state_dict_roundtrip():
    x, y = _toy(1000)
    p = gbdt.fit(x, y, gbdt.GBDTConfig(num_trees=5, depth=3))
    p2 = gbdt.from_state_dict(gbdt.to_state_dict(p))
    a = np.asarray(gbdt.predict_efficient(p, jnp.asarray(x[:32])))
    b = np.asarray(gbdt.predict_efficient(p2, jnp.asarray(x[:32])))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_decision_tree_and_rf():
    x, y = _toy(3000)
    dt = gbdt.fit_decision_tree(x, y, depth=6)
    rf = gbdt.fit_random_forest(x, y, num_trees=10, depth=5)
    for p in (dt, rf):
        pred = np.asarray(gbdt.predict_efficient(p, jnp.asarray(x)))
        assert np.isfinite(pred).all()
        assert float(np.mean((pred - y) ** 2)) < float(np.var(y))
