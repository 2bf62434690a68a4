"""QuantileForest: coverage, monotonicity, fast-path equivalence."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.qrf import QuantileForest


def _data(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, size=(n, 3))
    # heteroscedastic: scale grows with x0
    y = 100 * X[:, 0] + 20 * X[:, 1] + rng.normal(0, 5 + 30 * X[:, 0], n)
    return X, y


def test_upper_quantile_coverage():
    X, y = _data()
    qf = QuantileForest(n_trees=16, seed=1).fit(X[:2500], y[:2500])
    ub = qf.predict_quantile(X[2500:], 0.9)
    cover = np.mean(y[2500:] <= ub)
    assert 0.8 <= cover <= 0.99, cover


def test_median_tracks_mean_structure():
    X, y = _data(seed=2)
    qf = QuantileForest(n_trees=16, seed=1).fit(X, y)
    lo_x = np.array([[0.1, 0.5, 0.5]])
    hi_x = np.array([[0.9, 0.5, 0.5]])
    assert qf.predict_quantile(hi_x, 0.5)[0] > qf.predict_quantile(lo_x, 0.5)[0]


def test_quantile_monotone_in_q():
    X, y = _data(seed=3)
    qf = QuantileForest(n_trees=8, seed=1).fit(X, y)
    xs = X[:50]
    q10 = qf.predict_quantile(xs, 0.1)
    q50 = qf.predict_quantile(xs, 0.5)
    q90 = qf.predict_quantile(xs, 0.9)
    assert np.all(q10 <= q50 + 1e-9) and np.all(q50 <= q90 + 1e-9)


def test_single_row_fast_path_matches_batch():
    X, y = _data(seed=4)
    qf = QuantileForest(n_trees=8, seed=1).fit(X, y)
    batch = qf.predict_quantile(X[:16], 0.75)
    singles = np.array([qf.predict_quantile(X[i:i + 1], 0.75)[0]
                        for i in range(16)])
    np.testing.assert_allclose(batch, singles, rtol=1e-12)


def test_exact_pool_close_to_grid():
    X, y = _data(seed=5)
    qf = QuantileForest(n_trees=8, seed=1, keep_leaf_values=True).fit(X, y)
    grid = qf.predict_quantile(X[:32], 0.9)
    exact = qf.predict_quantile_exact(X[:32], 0.9)
    # grid averages per-tree leaf quantiles; should be within noise scale
    assert np.mean(np.abs(grid - exact)) < 0.35 * np.std(y)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_predictions_within_target_range(seed):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, size=(300, 2))
    y = rng.uniform(10, 20, size=300)
    qf = QuantileForest(n_trees=4, max_depth=4, seed=seed).fit(X, y)
    p = qf.predict_quantile(X[:20], 0.5)
    assert np.all(p >= y.min() - 1e-9) and np.all(p <= y.max() + 1e-9)
