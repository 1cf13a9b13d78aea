"""The tuning jobs' objective: a smooth bowl with ripples over the encoded
cube, its optimum and weights drawn from a seed (as in chip_smoke.py)."""

from __future__ import annotations

import numpy as np

from bench.reference import encode


def make(space, seed: int):
    rng = np.random.default_rng(seed)
    dim = len(encode(space, sample(space, rng, 1)[0]))
    center = rng.uniform(0.2, 0.8, dim)
    weight = rng.uniform(0.5, 2.0, dim)

    def objective(config) -> float:
        u = encode(space, config)
        return float(np.sum(weight * (u - center) ** 2)
                     + 0.1 * np.sum(np.sin(7.0 * u)))

    return objective


def sample(space, rng: np.random.Generator, n: int):
    """``n`` configurations drawn uniformly in the encoded cube (log-uniform
    on log-scaled parameters), decoded as a user's random search would."""
    out = []
    for _ in range(n):
        config = {}
        for p in space:
            if p["kind"] == "categorical":
                config[p["name"]] = p["choices"][int(rng.integers(len(p["choices"])))]
                continue
            u = float(rng.random())
            lo, hi = float(p["low"]), float(p["high"])
            if p["scaling"] == "log":
                v = float(np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo))))
            else:
                v = lo + u * (hi - lo)
            v = min(hi, max(lo, v))
            config[p["name"]] = (int(min(p["high"], max(p["low"], round(v))))
                                 if p["kind"] == "integer" else v)
        out.append(config)
    return out
