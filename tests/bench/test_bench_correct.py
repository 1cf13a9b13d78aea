"""What decides ``correct``, driven through a whole run at a small size on
the CPU (the harness's look for a chip skipped): sound runs pass, the
float32 control fails, and each fault planted under the timed path fails.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.run import run_cell

FLEET = "fleet-d4.16jobs-same-space"
REFILL = "paper-resnet-d4.refill-n500"
SEED = 2**33 + 11  # larger than 32 signed bits hold


def _run(small_cell, name=FLEET, control=False, **size):
    cell, cfg, mix = small_cell(name, **size)
    return run_cell(cell, cfg, mix, spec.limits(cell["name"]), SEED, 1.5,
                    False, require_tpu=False, control=control,
                    log=lambda msg: None)


def _failed(result):
    return [k for k, c in result["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("name", [
    c["name"] for c in spec.benchmark()["workloads"] if c["chips"] == 1])
def test_sound_run_is_correct_and_control_is_not(small_cell, name):
    # cells of several replicas: tests/bench/test_bench_fleet.py
    # 150 rows: conditioned like the cells' histories, where float32 fails
    result = _run(small_cell, name, control=True, history=150)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    limits = spec.limits(name)
    assert any(result["control"][k] > limits[k] for k in result["control"]), (
        result["control"], limits)


def test_state_left_unchanged_is_caught(small_cell, monkeypatch):
    # the posterior's alpha is not refreshed after rows are appended
    import repro.core.suggest as suggest_mod

    monkeypatch.setattr(suggest_mod, "refresh_alpha", lambda post, y: post)
    result = _run(small_cell)
    assert not result["correct"] and "alpha_gap" in _failed(result)


@pytest.fixture
def retrace():
    """Drop JAX's compiled programs around a test that patches traced code."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def test_half_the_draws_left_out_is_caught(small_cell, monkeypatch, retrace):
    # the acquisition averages over the first half of the GPHP draws only
    import repro.core.acquisition as acquisition

    mean = acquisition.integrate_over_samples
    monkeypatch.setattr(acquisition, "integrate_over_samples",
                        lambda v: mean(v[: max(1, v.shape[0] // 2)])
                        if v.ndim == 2 else v)
    result = _run(small_cell)
    assert not result["correct"] and "acq_excess" in _failed(result)


def test_altered_answer_is_caught(small_cell, monkeypatch):
    # the candidates move after they were scored
    import repro.core.suggest as suggest_mod

    acq = suggest_mod.optimize_acquisition

    def altered(*args):
        cands, vals = acq(*args)
        return np.clip(np.asarray(cands) + 0.05, 0.0, 1.0), vals

    monkeypatch.setattr(suggest_mod, "optimize_acquisition", altered)
    result = _run(small_cell)
    assert not result["correct"] and "acq_excess" in _failed(result)


def test_draws_that_ignore_the_rows_are_caught(small_cell, monkeypatch):
    # the GPHP fit returns its chain's start, whatever the rows say
    from bench.calibrate import plant_start_only
    from repro.core.suggest import BOSuggester

    monkeypatch.setattr(BOSuggester, "_fit_gphps", BOSuggester._fit_gphps)
    plant_start_only()
    result = _run(small_cell, REFILL, history=150)
    assert not result["correct"] and "fit_gap" in _failed(result)


def test_draws_from_no_fit_are_caught(small_cell, monkeypatch):
    # siblings adopt pooled draws that are no job's fit
    from repro.core.service import GPHPSamplePool

    publish = GPHPSamplePool.publish
    monkeypatch.setattr(GPHPSamplePool, "publish",
                        lambda pool, samples, *a: publish(pool, samples + 0.3,
                                                          *a))
    result = _run(small_cell)
    assert not result["correct"] and "draw_source" in _failed(result)


def test_pending_left_out_is_caught(small_cell, monkeypatch):
    # the acquisition no longer excludes the job's pending trials
    import repro.core.suggest as suggest_mod

    acq = suggest_mod.optimize_acquisition

    def no_pending(post, anchors, y_best, pending, pending_mask, key, cfg):
        return acq(post, anchors, y_best, pending,
                   jnp.zeros_like(pending_mask), key, cfg)

    monkeypatch.setattr(suggest_mod, "optimize_acquisition", no_pending)
    result = _run(small_cell)
    assert not result["correct"] and "acq_excess" in _failed(result)
