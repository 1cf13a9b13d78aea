"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gp.params import GPHyperParams
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.mamba_scan.ops import selective_scan
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.matern52.ops import matern52_cross, matern52_gram
from repro.kernels.matern52.ref import matern52_cross_ref, matern52_gram_ref
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref

pytestmark = pytest.mark.pallas

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------- matern52
@pytest.mark.parametrize("n,m,d", [(4, 4, 1), (64, 33, 5), (129, 257, 13), (200, 40, 31)])
@pytest.mark.parametrize("warp", [True, False])
def test_matern52_sweep(n, m, d, warp):
    x1 = jnp.asarray(RNG.random((n, d)))
    x2 = jnp.asarray(RNG.random((m, d)))
    p = GPHyperParams(
        log_lengthscale=jnp.asarray(RNG.normal(0, 0.5, d)),
        log_amplitude=jnp.asarray(0.4),
        log_noise=jnp.asarray(-3.0),
        log_warp_a=jnp.asarray(RNG.normal(0, 0.3, d)),
        log_warp_b=jnp.asarray(RNG.normal(0, 0.3, d)),
    )
    got = matern52_gram(x1, x2, p, warp=warp, interpret=True)
    want = matern52_gram_ref(x1, x2, p, warp=warp)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_matern52_identity_warp_dims():
    """One-hot dims (log a = log b = 0) must pass through unwarped."""
    d = 4
    x = jnp.asarray(RNG.random((32, d)))
    p = GPHyperParams(
        log_lengthscale=jnp.zeros(d),
        log_amplitude=jnp.asarray(0.0),
        log_noise=jnp.asarray(-3.0),
        log_warp_a=jnp.asarray([0.0, 0.5, 0.0, -0.5]),
        log_warp_b=jnp.asarray([0.0, 0.2, 0.0, 0.3]),
    )
    got = matern52_gram(x, x, p, interpret=True)
    want = matern52_gram_ref(x, x, p)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("m,d", [(1, 1), (40, 5), (129, 13), (300, 31)])
@pytest.mark.parametrize("warp", [True, False])
def test_matern52_cross_sweep(m, d, warp):
    """Cross-gram row kernel (rank-1 append path) vs one row of the oracle."""
    x_new = jnp.asarray(RNG.random(d))
    x_train = jnp.asarray(RNG.random((m, d)))
    p = GPHyperParams(
        log_lengthscale=jnp.asarray(RNG.normal(0, 0.5, d)),
        log_amplitude=jnp.asarray(0.3),
        log_noise=jnp.asarray(-3.0),
        log_warp_a=jnp.asarray(RNG.normal(0, 0.3, d)),
        log_warp_b=jnp.asarray(RNG.normal(0, 0.3, d)),
    )
    got = matern52_cross(x_new, x_train, p, warp=warp, interpret=True)
    want = matern52_cross_ref(x_new, x_train, p, warp=warp)
    assert got.shape == (m,)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("dtype,rel", [(jnp.float32, 4e-7), (jnp.float64, 1e-15)])
def test_exp_accurate(dtype, rel):
    """The kernels' eˣ (mul/add + exponent bitcast, because Mosaic's f32 exp
    is ~4e-6 off on a v5e) keeps the rounding of its dtype over the range
    the Matérn response, EI and Φ's tail use, and clamps below at e^-87."""
    from repro.kernels.matern52.kernel import exp_accurate

    x = np.concatenate([np.linspace(-87.0, 88.0, 200001), [0.0, -1e-9, 1e-9]])
    got = np.asarray(exp_accurate(jnp.asarray(x, dtype)), np.float64)
    want = np.exp(np.asarray(x, dtype).astype(np.float64))
    assert np.max(np.abs(got - want) / want) < rel
    assert float(exp_accurate(jnp.asarray(-200.0, dtype))) == pytest.approx(
        np.exp(-87.0), rel=1e-6
    )


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2.5e-7), (jnp.float64, 5e-16)])
def test_log_accurate(dtype, tol):
    """The wrappers' ln x (frexp + atanh series, because XLA's f32 log is
    ~3.5e-4 off on a v5e) keeps the rounding of its dtype, relative to
    max(|ln x|, 1), over the warp's inputs [1e-6, 1] and beyond."""
    from repro.kernels.matern52.kernel import log_accurate

    x = np.concatenate([np.geomspace(1e-7, 1e3, 100001), np.linspace(0.5, 2, 10001)])
    x = np.asarray(x, dtype)
    got = np.asarray(log_accurate(jnp.asarray(x)), np.float64)
    want = np.log(x.astype(np.float64))
    assert np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)) < tol


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "b,s,hq,hkv,dh,window,softcap",
    [
        (2, 128, 4, 2, 64, 0, 0.0),
        (1, 256, 8, 1, 128, 0, 0.0),
        (2, 384, 6, 2, 80, 100, 0.0),
        (1, 200, 2, 2, 64, 0, 0.0),
        (2, 256, 4, 2, 64, 0, 30.0),
        (1, 130, 4, 4, 96, 64, 20.0),
    ],
)
def test_flash_attention_sweep(b, s, hq, hkv, dh, window, softcap):
    q = jnp.asarray(RNG.standard_normal((b, s, hq, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, dh)), jnp.float32)
    got = flash_attention(q, k, v, window=window, softcap=softcap, interpret=True)
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    want = tr(flash_attention_ref(tr(q), tr(k), tr(v), window=window, softcap=softcap))
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2), (jnp.float32, 3e-5)])
def test_flash_attention_dtypes(dtype, tol):
    q = jnp.asarray(RNG.standard_normal((1, 256, 4, 128)), dtype)
    k = jnp.asarray(RNG.standard_normal((1, 256, 2, 128)), dtype)
    v = jnp.asarray(RNG.standard_normal((1, 256, 2, 128)), dtype)
    got = flash_attention(q, k, v, interpret=True).astype(jnp.float32)
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    want = tr(flash_attention_ref(tr(q), tr(k), tr(v))).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=tol)


# ---------------------------------------------------------- decode attention
@pytest.mark.parametrize(
    "b,hq,hkv,dh,c,fv",
    [(2, 8, 2, 64, 1024, 1.0), (1, 16, 1, 128, 2048, 0.5),
     (2, 4, 4, 80, 700, 0.8), (1, 14, 2, 64, 512, 1.0)],
)
def test_decode_attention_sweep(b, hq, hkv, dh, c, fv):
    q = jnp.asarray(RNG.standard_normal((b, hq, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, c, hkv, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, c, hkv, dh)), jnp.float32)
    valid = jnp.asarray(RNG.random((b, c)) < fv).at[:, 0].set(True)
    got = decode_attention(q, k, v, valid, interpret=True)
    want = decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(got, want, atol=3e-5)


# ---------------------------------------------------------------- mamba scan
@pytest.mark.parametrize("b,s,di,ds", [(2, 64, 128, 8), (1, 300, 256, 16), (2, 128, 300, 16)])
def test_mamba_scan_sweep(b, s, di, ds):
    u = jnp.asarray(RNG.standard_normal((b, s, di)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, s, di)) * 0.1, jnp.float32)
    a = jnp.asarray(-RNG.random((di, ds)) * 2, jnp.float32)
    b_t = jnp.asarray(RNG.standard_normal((b, s, ds)), jnp.float32)
    c_t = jnp.asarray(RNG.standard_normal((b, s, ds)), jnp.float32)
    got = selective_scan(u, dt, a, b_t, c_t, interpret=True)
    want = mamba_scan_ref(u, dt, a, b_t, c_t)
    np.testing.assert_allclose(got, want, atol=1e-4)


# ---------------------------------------------------------------- rglru scan
@pytest.mark.parametrize("b,s,di", [(2, 64, 128), (1, 500, 256), (2, 129, 300)])
def test_rglru_scan_sweep(b, s, di):
    a = jnp.asarray(RNG.uniform(0.01, 0.9999, (b, s, di)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((b, s, di)), jnp.float32)
    got = rglru_scan(a, g, interpret=True)
    want = rglru_scan_ref(a, g)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_rglru_extreme_decays():
    """Near-0 and near-1 decays over a long sequence (stability)."""
    b, s, di = 1, 384, 256
    a = jnp.concatenate([
        jnp.full((b, s, di // 2), 0.9999, jnp.float32),
        jnp.full((b, s, di // 2), 1e-4, jnp.float32),
    ], axis=-1)
    g = jnp.asarray(RNG.standard_normal((b, s, di)), jnp.float32)
    got = rglru_scan(a, g, interpret=True)
    want = rglru_scan_ref(a, g)
    np.testing.assert_allclose(got, want, atol=1e-3)
