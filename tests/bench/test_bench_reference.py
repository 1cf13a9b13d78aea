"""The reference's own GP fit: its gradient, and that it fits."""

import numpy as np

from bench.reference import _neg_lml, best_fit


def _rows(n=24, d=3, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.random((n, d))
    y = np.sin(4.0 * x[:, 0]) + x[:, 1] ** 2
    return x, (y - y.mean()) / y.std()


def test_lml_gradient_matches_finite_differences():
    x, y = _rows()
    theta = np.array([-0.3, 0.2, 0.5, 0.1, np.log(0.05)])
    _, grad = _neg_lml(theta, x, y)
    eps = 1e-6
    for i in range(len(theta)):
        step = np.zeros_like(theta)
        step[i] = eps
        fd = (_neg_lml(theta + step, x, y)[0]
              - _neg_lml(theta - step, x, y)[0]) / (2 * eps)
        assert abs(fd - grad[i]) <= 1e-5 * max(1.0, abs(fd)), (i, fd, grad[i])


def test_best_fit_beats_its_starts():
    x, y = _rows()
    start = np.array([0.0, 0.0, 0.0, 0.0, np.log(1e-2)])
    assert best_fit(x, y) > -_neg_lml(start, x, y)[0] + 1.0
