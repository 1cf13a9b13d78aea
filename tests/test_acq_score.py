"""Oracle-parity suite for the fused predict+EI/LCB anchor-scoring kernel.

Three-way triangulation per configuration:

    Pallas kernel (interpret)  vs  kernels/acq_score/ref.py (standalone jnp)
    Pallas kernel (interpret)  vs  gp.predict + acquisition composition

swept over shape buckets, GPHP sample counts, input dims and both closed-form
acquisitions — tolerance 1e-5 (measured parity is ~4e-8 under the x64 test
session: the kernel's Φ is accurate to f32 rounding, in f64 too). Plus end-to-end invariance: a ``BOSuggester`` scoring anchors with
``backend="pallas"`` must pick the same candidates as ``backend="xla"`` on a
fixed seed, including the ``suggest_batch(k)`` fantasy path.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BOConfig,
    BOSuggester,
    Continuous,
    ObservationStore,
    SearchSpace,
)
from repro.core import acquisition as A
from repro.core.gp import gp as G
from repro.core.gp import params as P
from repro.core.gp.slice_sampler import SliceSamplerConfig
from repro.core.optimize_acq import AcqOptConfig
from repro.kernels.acq_score.ops import acq_score
from repro.kernels.acq_score.ref import acq_score_ref

pytestmark = pytest.mark.pallas

ATOL = 1e-5
TINY_SLICE = SliceSamplerConfig(num_samples=12, burn_in=6, thin=2)


def _posterior(bucket: int, n_live: int, d: int, S: int, seed: int = 0):
    """Shape-bucketed posterior with random GPHP draws (warping active)."""
    rng = np.random.default_rng(seed)
    x = np.zeros((bucket, d))
    y = np.zeros(bucket)
    x[:n_live] = rng.random((n_live, d))
    y[:n_live] = rng.standard_normal(n_live)
    mask = np.zeros(bucket, dtype=bool)
    mask[:n_live] = True
    if S == 0:  # unbatched single-GPHP posterior
        p = P.GPHyperParams.unpack(
            P.default_params(d).pack() + 0.1 * rng.standard_normal(3 * d + 2), d
        )
        post = G.fit_gp(jnp.asarray(x), jnp.asarray(y), p, jnp.asarray(mask))
    else:
        packed = jnp.stack(
            [
                P.default_params(d).pack() + 0.1 * rng.standard_normal(3 * d + 2)
                for _ in range(S)
            ]
        )
        pb = P.GPHyperParams.unpack(packed, d)
        post = G.fit_posterior_batch(
            jnp.asarray(x), jnp.asarray(y), pb, jnp.asarray(mask)
        )
    y_best = jnp.asarray(float(y[:n_live].min()))
    anchors = jnp.asarray(rng.random((200, d)))  # non-tile-multiple: trims pad
    return post, anchors, y_best


def _composition(post, anchors, y_best, acq):
    mu, var = G.predict(post, anchors, backend="xla")
    if acq == "ei":
        return A.expected_improvement(mu, var, y_best)
    return A.lcb(mu, var, 2.0)


@pytest.mark.parametrize(
    "bucket,n_live",
    [(8, 5), (64, 50), pytest.param(256, 200, marks=pytest.mark.slow)],
)
@pytest.mark.parametrize("S", [1, 8])
@pytest.mark.parametrize("d", [2, 12])
@pytest.mark.parametrize("acq", ["ei", "lcb"])
def test_parity_sweep(bucket, n_live, S, d, acq):
    post, anchors, y_best = _posterior(bucket, n_live, d, S, seed=bucket + S + d)
    got = acq_score(post, anchors, y_best, acq=acq, backend="pallas")
    ref = acq_score_ref(post, anchors, y_best, acq=acq)
    comp = _composition(post, anchors, y_best, acq)
    assert got.shape == (S, 200)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=ATOL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(comp), atol=ATOL)


def test_unbatched_posterior_shape_and_parity():
    post, anchors, y_best = _posterior(64, 40, 3, S=0)
    got = acq_score(post, anchors, y_best, acq="ei", backend="pallas")
    ref = acq_score_ref(post, anchors, y_best, acq="ei")
    assert got.shape == (200,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=ATOL)


def test_xla_backend_is_the_composition():
    """backend="xla" must be the production predict+EI path, exactly."""
    post, anchors, y_best = _posterior(64, 50, 4, S=4)
    for acq in ("ei", "lcb"):
        got = acq_score(post, anchors, y_best, acq=acq, backend="xla")
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(_composition(post, anchors, y_best, acq))
        )


def test_argmax_anchor_invariant_across_backends():
    post, anchors, y_best = _posterior(64, 50, 5, S=8, seed=3)
    for acq in ("ei", "lcb"):
        v_x = A.integrate_over_samples(
            acq_score(post, anchors, y_best, acq=acq, backend="xla")
        )
        v_p = A.integrate_over_samples(
            acq_score(post, anchors, y_best, acq=acq, backend="pallas")
        )
        assert int(jnp.argmax(v_x)) == int(jnp.argmax(v_p))


def test_cached_inverse_path_matches_recomputed():
    """``chol_inv`` threaded from the engine (built at refit, O(n²)-maintained
    by the rank-1 append, identity-padded on growth) must score identically
    to the invert-on-call fallback."""
    from repro.core.gp.incremental import grow_posterior, posterior_append

    rng = np.random.default_rng(11)
    n0, nb, d, S = 10, 16, 3, 4
    x = np.zeros((nb, d))
    y = np.zeros(nb)
    x[:n0] = rng.random((n0, d))
    y[:n0] = rng.standard_normal(n0)
    mask = np.zeros(nb, dtype=bool)
    mask[:n0] = True
    packed = jnp.stack(
        [P.default_params(d).pack() + 0.1 * rng.standard_normal(3 * d + 2)
         for _ in range(S)]
    )
    post = G.fit_posterior_batch(
        jnp.asarray(x), jnp.asarray(y),
        P.GPHyperParams.unpack(packed, d), jnp.asarray(mask),
        with_inverse=True,
    )
    for _ in range(4):  # grows past the 16-bucket once
        if int(jnp.sum(post.mask)) >= post.x_train.shape[0]:
            post = grow_posterior(post, post.x_train.shape[0] * 2)
        post = posterior_append(post, jnp.asarray(rng.random(d)))
    assert post.chol_inv is not None
    for s in range(S):  # the maintained inverse is the factor's inverse
        np.testing.assert_allclose(
            np.asarray(post.chol_inv[s]),
            np.linalg.inv(np.asarray(post.chol[s])),
            atol=1e-10,
        )
    anchors = jnp.asarray(rng.random((64, d)))
    y_best = jnp.asarray(-0.5)
    cached = acq_score(post, anchors, y_best, backend="pallas")
    recomputed = acq_score(
        post._replace(chol_inv=None), anchors, y_best, backend="pallas"
    )
    np.testing.assert_allclose(np.asarray(cached), np.asarray(recomputed), atol=1e-10)


def test_ndtr_f32_matches_f64_normal_cdf():
    """The kernel's Φ (rational erf/erfc, Mosaic-lowerable) in f32, the
    chip's dtype: absolute error at f32 rounding everywhere, and relative
    accuracy in the lower tail where EI lives far below the incumbent."""
    import jax

    from repro.kernels.acq_score.kernel import ndtr

    z = np.linspace(-12.0, 8.0, 40001)
    got = np.asarray(ndtr(jnp.asarray(z, jnp.float32)), np.float64)
    ref = np.asarray(jax.scipy.special.ndtr(jnp.asarray(z)))
    assert np.max(np.abs(got - ref)) < 2e-7
    tail = z < -1.0
    rel = np.abs(got[tail] - ref[tail]) / ref[tail]
    # exp(−z²/2) carries the f32 rounding of z² in its exponent, as XLA's
    # own f32 erfc does: relative error ~ ε·z² (z = −12 is Φ ~ 1e-33)
    assert np.all(rel < 1e-6 + 2e-7 * z[tail] ** 2)


@pytest.mark.parametrize("acq", ["ei", "lcb"])
def test_row_tiled_factor_f32_parity(monkeypatch, acq):
    """f32 interpret mode (the chip's arithmetic) with the inverse factor
    streamed in 8-row blocks over 8 grid steps and two anchor tiles must
    match the one-block f32 run up to summation order, and the f64 oracle
    to f32 accuracy on this posterior (|α| ~ 2e3, so K*·α carries ~1e-3)."""
    import jax

    from repro.kernels.acq_score import kernel as K

    post, anchors, y_best = _posterior(64, 50, 4, S=3, seed=5)
    post = post._replace(chol_inv=G._triangular_inverse(post.chol))
    post32 = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.float64 else a, post
    )
    args = (post32, anchors.astype(jnp.float32), y_best.astype(jnp.float32))
    whole = acq_score(*args, acq=acq, backend="pallas")
    monkeypatch.setattr(K, "_VMEM_TILE_ELEMS", 512)
    assert K.tiling(200, 64) == (256, 128, 64, 8)
    tiled = acq_score(*args, acq=acq, backend="pallas")
    assert tiled.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(whole), atol=5e-5)
    ref = acq_score_ref(post, anchors, y_best, acq=acq)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(ref), atol=2e-3)


def test_tiling_bounds_vmem_blocks():
    """Every bucket gets row and anchor tiles that divide the padded sizes
    and keep each VMEM block within about the tile budget."""
    from repro.kernels.acq_score.kernel import _VMEM_TILE_ELEMS, tiling

    for n in (5, 200, 512, 1500, 2048, 4096, 8192, 16384):
        mpad, tile_a, npad, tile_r = tiling(1024, n)
        assert npad >= n and npad % tile_r == 0 and tile_r % 8 == 0
        assert mpad >= 1024 and mpad % tile_a == 0 and tile_a % 128 == 0
        assert tile_r * npad <= 2 * _VMEM_TILE_ELEMS
        assert tile_a * npad <= 2 * _VMEM_TILE_ELEMS or tile_a == 128


def test_rejects_unsupported():
    post, anchors, y_best = _posterior(8, 5, 2, S=1)
    with pytest.raises(ValueError):
        acq_score(post, anchors, y_best, acq="ts", backend="pallas")
    with pytest.raises(ValueError):
        acq_score(post, anchors, y_best, backend="cuda")


# --------------------------------------------------------------- end-to-end
def _run_engine(backend: str, pending_strategy: str, k: int = 2):
    """Fixed-seed decisions; only the anchor-scoring backend varies."""
    space = SearchSpace([Continuous(f"x{i}", 0.0, 1.0) for i in range(3)])
    store = ObservationStore(space)
    rng = np.random.default_rng(7)
    for c in space.sample(rng, 10):
        store.push(c, float(sum((c[f"x{i}"] - 0.4) ** 2 for i in range(3))))
    cfg = BOConfig(
        num_init=3,
        slice_config=TINY_SLICE,
        acq=AcqOptConfig(num_anchors=128, num_refine=4, refine_steps=5),
        backend=backend,
        pending_strategy=pending_strategy,
    )
    sugg = BOSuggester(space, cfg, seed=0, store=store)
    first = sugg.suggest_batch(k)  # batched refill: slot 2+ sees fantasies
    for i, c in enumerate(first):
        store.mark_pending(i, c)
    second = sugg.suggest_batch(1)  # decision with live pending candidates
    return first + second


@pytest.mark.slow
@pytest.mark.parametrize("pending_strategy", ["exclude", "liar"])
def test_suggester_backend_invariance(pending_strategy):
    """backend="pallas" (interpret) and backend="xla" pick the same anchors
    end to end — same GPHP chain (shared fit_backend), same argmax — through
    both the pending path and the suggest_batch(k) fantasy path."""
    got_x = _run_engine("xla", pending_strategy)
    got_p = _run_engine("pallas", pending_strategy)
    assert len(got_x) == len(got_p) == 3
    for cx, cp in zip(got_x, got_p):
        assert cx.keys() == cp.keys()
        np.testing.assert_allclose(
            [cx[key] for key in sorted(cx)],
            [cp[key] for key in sorted(cp)],
            atol=1e-9,
        )


def test_boconfig_backend_shorthand():
    import dataclasses

    cfg = BOConfig(backend="pallas")
    assert cfg.acq.backend == "pallas"
    assert cfg.fit_backend == "xla"  # fitting decoupled from scoring
    cfg2 = BOConfig(acq=AcqOptConfig(backend="pallas"))
    assert cfg2.acq.backend == "pallas"
    # the shorthand is one-shot: a later explicit acq override must win
    cfg3 = dataclasses.replace(cfg, acq=AcqOptConfig(backend="xla"))
    assert cfg3.acq.backend == "xla"
    assert cfg.fast().acq.backend == "pallas"  # and replace() keeps folded acq
