"""Plain reference of one decision, in numpy, from the GP and EI equations.

Nothing here imports the program. Given a job's completed trials (the
harness's own configurations and objective values), the GPHP draws the
decision used and the candidates it returned, it recomputes in ``dtype``:

* the encoding of each configuration into the unit cube (log, linear,
  integer, one-hot), and the standardized targets;
* per draw, the Kumaraswamy-warped Matérn-5/2 ARD gram K + (σ₀² + 1e-8)I,
  its Cholesky factor L, L⁻¹ and α = K⁻¹y (paper §4.2);
* integrated EI at the candidates: the mean over draws of
  σ(γΦ(γ) + φ(γ)), γ = (y* − μ)/σ, with μ = k*ᵀα, σ² = amp² − ‖L⁻¹k*‖²,
  and −inf within the exclusion radius of a pending candidate (§4.3-4.4).

The GPHP draws themselves are judged by how well they fit the job's rows:
the reference fits its own GP by type-II maximum likelihood (Matérn-5/2
ARD without warping, a model nested in the warped one, L-BFGS-B with
analytic gradients, two starts) and reads how far the worst draw's log
marginal likelihood lies below that fit (``fit_gap``, in nats). Draws of
the warped posterior sit a few nats either side of it; draws that ignore
the rows, or were fit to far fewer of them, sit tens to thousands of nats
below.

float64 is the reference, and float32 its yardstick for the acquisition
(``decision_gaps``). The control is the same reference one precision
below what the configurations state: the GP in float32 (below float64), and
the kernel's two contractions, μ = k*ᵀα and L⁻¹k*, at ``Precision.HIGH``,
three bfloat16 passes (below the kernel's float32 at HIGHEST).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Sequence

import numpy as np
from scipy.linalg import cho_solve, solve_triangular
from scipy.optimize import minimize
from scipy.special import ndtr

SQRT5 = math.sqrt(5.0)
JITTER = 1e-8  # added to the noise variance on the live diagonal
WARP_EPS = 1e-6  # the warp's clip away from the cube's faces
STD_FLOOR = 1e-12
LOG_2PI = math.log(2.0 * math.pi)
F32_TINY = float(np.finfo(np.float32).tiny)
F32_EPS = float(np.finfo(np.float32).eps)
# the model's boxes on the log lengthscales, amplitude and noise std (paper
# §4.2: "we fix upper and lower bounds on the GPHPs for numerical stability")
LOG_LENGTHSCALE = (math.log(0.01), math.log(30.0))
LOG_AMPLITUDE = (math.log(0.05), math.log(20.0))
LOG_NOISE = (math.log(1e-4), 0.0)


# ------------------------------------------------------------------ space


def encode(space: Sequence[Mapping[str, Any]], config: Mapping[str, Any]):
    """A configuration as a float64 point of the unit cube."""
    out: List[float] = []
    for p in space:
        v = config[p["name"]]
        if p["kind"] == "categorical":
            out.extend(1.0 if c == v else 0.0 for c in p["choices"])
            continue
        lo, hi, v = float(p["low"]), float(p["high"]), float(v)
        if p["scaling"] == "log":
            u = (math.log(v) - math.log(lo)) / (math.log(hi) - math.log(lo))
        else:
            u = (v - lo) / (hi - lo)
        out.append(min(1.0, max(0.0, u)))
    return np.asarray(out, np.float64)


def in_bounds(space: Sequence[Mapping[str, Any]], config: Mapping[str, Any]):
    """Whether ``config`` names exactly the space's parameters, each a valid
    value of its own range or choices."""
    if set(config) != {p["name"] for p in space}:
        return False
    for p in space:
        v = config[p["name"]]
        if p["kind"] == "categorical":
            if v not in p["choices"]:
                return False
        elif p["kind"] == "integer":
            if not isinstance(v, int) or not p["low"] <= v <= p["high"]:
                return False
        elif not (isinstance(v, float) and math.isfinite(v)
                  and p["low"] <= v <= p["high"]):
            return False
    return True


def standardize(y: np.ndarray) -> np.ndarray:
    std = float(np.std(y))
    return (y - np.mean(y)) / (std if std > STD_FLOOR else 1.0)


# --------------------------------------------------------------------- GP


def _bf16(x):
    """float32 rounded to bfloat16 (to nearest, ties to even), as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) & np.uint32(
        0xFFFF0000)
    return b.view(np.float32)


def dot_high(a, b):
    """a @ b at Precision.HIGH: three bfloat16 passes, float32 sums."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return ah @ bh + (ah @ bl + al @ bh)


def _warp(x, log_a, log_b):
    identity = (np.abs(log_a) < 1e-7) & (np.abs(log_b) < 1e-7)
    a, b = np.exp(log_a), np.exp(log_b)
    xc = np.clip(x, WARP_EPS, 1.0 - WARP_EPS)
    xa = np.clip(xc ** a, WARP_EPS, 1.0 - WARP_EPS)
    return np.where(identity, x, 1.0 - (1.0 - xa) ** b)


def _scaled(x, draw):
    return _warp(x, draw["log_warp_a"], draw["log_warp_b"]) * np.exp(
        -draw["log_lengthscale"])


def _matern(a, b, amp2):
    diff = a[:, None, :] - b[None, :, :]
    r2 = np.sum(diff * diff, axis=-1)
    r = np.sqrt(np.maximum(r2, 1e-30))
    return amp2 * (1.0 + SQRT5 * r + (5.0 / 3.0) * r2) * np.exp(-SQRT5 * r)


def draws(params: Mapping[str, np.ndarray], dtype) -> List[Dict[str, Any]]:
    """Per-draw GPHP dicts from arrays with a leading draw axis."""
    s = np.asarray(params["log_amplitude"]).shape[0]
    return [{k: np.asarray(v, dtype)[i] for k, v in params.items()}
            for i in range(s)]


def posterior(x, y_std, params, dtype=np.float64) -> List[Dict[str, Any]]:
    """L, L⁻¹ and α of every draw over the rows ``x`` with targets ``y_std``."""
    x = np.asarray(x, dtype)
    y = np.asarray(y_std, dtype)
    out = []
    for d in draws(params, dtype):
        xs = _scaled(x, d)
        amp2 = np.exp(2.0 * d["log_amplitude"])
        k = _matern(xs, xs, amp2)
        k = k + (np.exp(2.0 * d["log_noise"]) + dtype(JITTER)) * np.eye(
            len(x), dtype=dtype)
        chol = np.linalg.cholesky(k).astype(dtype)
        linv = solve_triangular(chol, np.eye(len(x), dtype=dtype), lower=True)
        alpha = linv.T @ (linv @ y)
        lml = (-0.5 * float(y @ alpha) - float(np.sum(np.log(np.diag(chol))))
               - 0.5 * len(x) * LOG_2PI)
        out.append(dict(draw=d, xs=xs, amp2=amp2, chol=chol, linv=linv,
                        alpha=alpha, lml=lml))
    return out


def _neg_lml(theta, x, y):
    """−log p(y | x, θ) and its gradient for the unwarped Matérn-5/2 ARD GP,
    θ = (log lengthscales, log amplitude, log noise std)."""
    n, d = x.shape
    ls, amp2 = np.exp(theta[:d]), math.exp(2.0 * theta[d])
    noise2 = math.exp(2.0 * theta[d + 1])
    xs = x / ls
    sq = np.sum(xs * xs, axis=1)
    r2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * (xs @ xs.T), 0.0)
    np.fill_diagonal(r2, 0.0)
    r = np.sqrt(r2)
    decay = np.exp(-SQRT5 * r)
    k_sig = amp2 * (1.0 + SQRT5 * r + (5.0 / 3.0) * r2) * decay
    k = k_sig + (noise2 + JITTER) * np.eye(n)
    try:
        chol = np.linalg.cholesky(k)
    except np.linalg.LinAlgError:
        return 1e30, np.zeros_like(theta)
    alpha = cho_solve((chol, True), y)
    lml = (-0.5 * float(y @ alpha) - float(np.sum(np.log(np.diag(chol))))
           - 0.5 * n * LOG_2PI)
    # d lml / d θ_i = ½ Σ (ααᵀ − K⁻¹) ∘ ∂K/∂θ_i
    a = np.outer(alpha, alpha) - cho_solve((chol, True), np.eye(n))
    # ∂k/∂log ℓ_j = (5/3) amp² (1 + √5 r) e^{−√5 r} (xs_ij − xs_kj)²
    w = a * ((5.0 / 3.0) * amp2 * (1.0 + SQRT5 * r) * decay)
    g_ls = np.sum(xs * xs * np.sum(w, axis=1)[:, None], axis=0) - np.sum(
        xs * (w @ xs), axis=0)
    g_amp = float(np.sum(a * k_sig))
    g_noise = float(np.trace(a)) * noise2
    grad = np.concatenate([g_ls, [g_amp, g_noise]])
    return -lml, -grad


def best_fit(x, y) -> float:
    """The largest log marginal likelihood of the unwarped Matérn-5/2 ARD GP
    over the rows, within the model's boxes: L-BFGS-B from two starts."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    d = x.shape[1]
    box = [LOG_LENGTHSCALE] * d + [LOG_AMPLITUDE, LOG_NOISE]
    best = -math.inf
    for log_ls in (0.0, math.log(0.3)):
        theta0 = np.concatenate([np.full(d, log_ls), [0.0, math.log(1e-2)]])
        res = minimize(_neg_lml, theta0, args=(x, y), jac=True,
                       method="L-BFGS-B", bounds=box,
                       options={"maxiter": 200})
        best = max(best, -float(res.fun))
    return best


def integrated_ei(post, x, cands, y_best, pending, radius, dtype=np.float64,
                  dot=np.matmul):
    """Integrated EI at ``cands``: (m,), −inf near a pending point."""
    cands = np.asarray(cands, dtype)
    total = np.zeros(len(cands), dtype)
    for p in post:
        cs = _scaled(cands, p["draw"])
        kstar = _matern(np.asarray(p["xs"], dtype), cs, p["amp2"])  # (n, m)
        mu = dot(kstar.T, p["alpha"])
        v = dot(p["linv"], kstar)
        var = np.maximum(p["amp2"] - np.sum(v * v, axis=0), dtype(1e-12))
        sigma = np.sqrt(np.maximum(var, dtype(1e-16)))
        gamma = (dtype(y_best) - mu) / sigma
        pdf = np.exp(-0.5 * gamma * gamma) / dtype(math.sqrt(2.0 * math.pi))
        ei = sigma * (gamma * ndtr(gamma) + pdf)
        total += np.maximum(ei, dtype(0.0)).astype(dtype)
    vals = (total / dtype(len(post))).astype(np.float64)
    if len(pending):
        dist = np.max(np.abs(cands[:, None, :].astype(np.float64)
                             - np.asarray(pending)[None, :, :]), axis=-1)
        vals = np.where(np.any(dist < radius, axis=1), -np.inf, vals)
    return vals


# ----------------------------------------------------------- comparisons


def relgap(got, want, floor: float = 0.0) -> float:
    """max |got − want| over max |want| (at least ``floor``): the widest gap,
    relative to scale."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), floor)
    return float(np.max(np.abs(got - want))) / (scale if scale > 0 else 1.0)


def fit_gap(x, y, params) -> float:
    """How far, in nats, the worst of the GPHP draws ``params`` lies below
    ``best_fit`` in log marginal likelihood over the rows ``x``, ``y``."""
    return best_fit(x, y) - min(p["lml"] for p in posterior(x, y, params))


def _at_float32(post, x, tilt: float = 0.0) -> List[Dict[str, Any]]:
    """The posterior at float32: the rows, the draws, L⁻¹ and α rounded to
    it (the last two first scaled by 1 ± ``tilt``, so that each rounds
    another way), the rows warped and scaled and the signal variance taken
    in float32, as the kernel's wrapper does."""
    out = []
    for p in post:
        d = {k: np.asarray(v, np.float32) for k, v in p["draw"].items()}
        out.append(dict(draw=d, xs=_scaled(np.asarray(x, np.float32), d),
                        amp2=np.exp(np.float32(2.0) * d["log_amplitude"]),
                        linv=np.asarray(p["linv"] * (1.0 + tilt), np.float32),
                        alpha=np.asarray(p["alpha"] * (1.0 - tilt),
                                         np.float32)))
    return out


def decision_gaps(call, rows_x, rows_y, pending, space, control=False):
    """Gaps of one recorded decision slot against the float64 reference.

    ``rows_x``, ``rows_y`` and ``pending`` are the job's completed and
    pending configurations when it asked, from the client's own record.
    ``call`` holds what the program used and produced: its draws
    (``params``), live factor (``chol``, ``chol_inv``, ``alpha``),
    candidates and their acquisition values. With ``control`` the gaps read
    are those of the control (the module's docstring), put in the program's
    place at the same draws and candidates; a control whose float32
    Cholesky fails reads infinity.

    ``acq_excess`` is the acquisition's widest error against the float64
    reference over the widest error that float32 itself makes on the same
    call (the reference at the stated precision, in three roundings): the
    error of float32 grows with how ill-conditioned the draws are, from
    seed to seed, so it is the yardstick."""
    x = np.stack([encode(space, c) for c in rows_x])
    y = standardize(np.asarray(rows_y, np.float64))
    ref = posterior(x, y, call["params"])
    y_best = float(np.min(y))
    pend = np.stack([encode(space, c) for c in pending]) if pending else \
        np.zeros((0, x.shape[1]))
    args = (call["cands"], y_best, pend, call["radius"])
    want = integrated_ei(ref, x, *args)
    if control:
        try:
            low = posterior(x, y, call["params"], np.float32)
        except np.linalg.LinAlgError:
            return {"factor_gap": math.inf, "alpha_gap": math.inf,
                    "acq_excess": math.inf}
        chol = [p["chol"] for p in low]
        linv = [p["linv"] for p in low]
        alpha = [p["alpha"] for p in low]
        got = integrated_ei(low, x, *args, dtype=np.float32, dot=dot_high)
    else:
        chol, linv, alpha = call["chol"], call["chol_inv"], call["alpha"]
        got = np.asarray(call["vals"], np.float64)
    factor = max(max(relgap(chol[s], r["chol"]), relgap(linv[s], r["linv"]))
                 for s, r in enumerate(ref))
    alpha_gap = max(relgap(alpha[s], r["alpha"]) for s, r in enumerate(ref))
    finite = np.isfinite(want)

    def error(vals):
        vals = np.asarray(vals, np.float64)
        if not np.array_equal(finite, np.isfinite(vals)):
            return math.inf  # masked where the reference is not, or the reverse
        return float(np.max(np.abs(vals[finite] - want[finite]), initial=0.0))

    # the kernel computes σ² = amp² − ‖L⁻¹k*‖² in float32 over n rows, so it
    # resolves σ to about sqrt(n·eps)·amp: the scale of the acquisition is
    # never taken below that (nor below float32's smallest normal)
    amp = max(math.sqrt(float(r["amp2"])) for r in ref)
    scale = max(float(np.max(np.abs(want[finite]), initial=0.0)), F32_TINY,
                math.sqrt(len(x) * F32_EPS) * amp)
    own = max(error(integrated_ei(_at_float32(ref, x, tilt), x, *args,
                                  dtype=np.float32))
              for tilt in (0.0, F32_EPS, -F32_EPS))
    acq = error(got) / max(own, F32_EPS * scale)
    return {"factor_gap": factor, "alpha_gap": alpha_gap, "acq_excess": acq}
