"""Compile the decision path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler lowers each kernel for a v5e that is
only described (``jax.experimental.topologies``), with x64 on as the session
has it. What Mosaic would refuse on the chip — an illegal block shape, an op
with no TPU lowering, an f64 constant inside the body, more VMEM than a
kernel may use — fails here. Nothing runs, so these tests say nothing about
results or times; the interpret-mode parity suites cover results.

The topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""

import base64
import hashlib
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.gp.gp import GPPosterior
from repro.core.gp.params import GPHyperParams
from repro.core.optimize_acq import MultiMetricHead
from repro.kernels.acq_score.ops import acq_score, acq_score_multi
from repro.kernels.matern52.ops import matern52_cross, matern52_gram

pytestmark = pytest.mark.pallas

D = 8  # search-space width of the paper-default engine cell
M_ANCHORS = 1024  # the paper's Sobol anchor count


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without that chip; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.float64):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _posterior(sharding, s, n):
    """Abstract S-sample posterior in the engine's layout (f64 under x64,
    with the cached inverse factor the pallas engine threads through)."""
    def f(*shape, dtype=jnp.float64):
        return _spec(sharding, shape, dtype)

    params = GPHyperParams(f(s, D), f(s), f(s), f(s, D), f(s, D))
    return GPPosterior(
        x_train=f(n, D), mask=f(n, dtype=jnp.bool_), chol=f(s, n, n),
        alpha=f(s, n), params=params, chol_inv=f(s, n, n),
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("acq", ["ei", "lcb"])
@pytest.mark.parametrize("npad,s", [(256, 10), (2048, 10), (8192, 2)])
def test_acq_score_compiles(one_chip, npad, s, acq):
    post = _posterior(one_chip, s, npad)
    x = _spec(one_chip, (M_ANCHORS, D))
    y_best = _spec(one_chip, ())
    _compile(
        lambda p, x, y: acq_score(
            p, x, y, acq=acq, backend="pallas", interpret=False
        ),
        post, x, y_best,
    )


_HEADS = {  # mode -> (M heads, constraints, weights rows x cols)
    "constrained": (3, 2, (0, 1)),
    "pareto": (3, 1, (16, 2)),
    "rungs": (4, 0, (1, 4)),
    "cost": (2, 0, (1, 1)),
}


@pytest.mark.parametrize("mode", sorted(_HEADS))
def test_acq_score_multi_compiles(one_chip, mode):
    s, npad = 10, 256
    heads, cons, (w_rows, w_cols) = _HEADS[mode]
    yw = heads if mode == "rungs" else w_rows
    head = MultiMetricHead(
        alphas=_spec(one_chip, (s, heads, npad)),
        t_std=_spec(one_chip, (cons,)),
        y_best=_spec(one_chip, ()),
        has_feasible=_spec(one_chip, (), jnp.bool_),
        weights=_spec(one_chip, (w_rows, w_cols)),
        y_best_w=_spec(one_chip, (yw,)),
    )
    _compile(
        lambda p, h, x: acq_score_multi(
            p, h, x, mode=mode, backend="pallas", interpret=False
        ),
        _posterior(one_chip, s, npad), head, _spec(one_chip, (M_ANCHORS, D)),
    )


def _params(sharding):
    return GPHyperParams(
        _spec(sharding, (D,)), _spec(sharding, ()), _spec(sharding, ()),
        _spec(sharding, (D,)), _spec(sharding, (D,)),
    )


def test_matern52_gram_compiles(one_chip):
    x = _spec(one_chip, (512, D))
    _compile(
        lambda a, b, p: matern52_gram(a, b, p, interpret=False),
        x, x, _params(one_chip),
    )


def test_matern52_cross_compiles(one_chip):
    _compile(
        lambda a, b, p: matern52_cross(a, b, p, interpret=False),
        _spec(one_chip, (D,)), _spec(one_chip, (2048, D)), _params(one_chip),
    )


def _cache_key_ir(lowered):
    """The part of JAX's persistent-cache key that hashes the program."""
    from jax._src.cache_key import IgnoreCallbacks, _canonicalize_ir

    ir = _canonicalize_ir(lowered.compiler_ir("stablehlo"), IgnoreCallbacks.NO)
    return hashlib.sha256(ir).hexdigest()


def _mosaic_payload(lowered):
    """The serialized Mosaic module of the program's (one) kernel."""
    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    yield inner
                    yield from walk(inner)

    (config,) = [
        op.attributes["backend_config"].value
        for op in walk(lowered.compiler_ir("stablehlo").operation)
        if op.name == "stablehlo.custom_call"
    ]
    return base64.b64decode(json.loads(config)["custom_call_config"]["body"])


@pytest.mark.parametrize("stable", [False, True])
def test_kernel_cache_key_ignores_caller(one_chip, stable):
    """A kernel's Mosaic payload carries source locations, which the
    persistent cache's key hashes. By default they hold the caller's frames
    and the checkout's absolute path, so the same kernel misses the cache
    when another script drives it or the checkout moves; under the entry
    points' ``stable_source_locations`` it is one key with no path."""
    from repro.compile_cache import stable_source_locations

    checkout = Path(__file__).resolve().parents[1]
    x = _spec(one_chip, (512, D))

    def lower(depth):
        if depth:
            return lower(depth - 1)
        fn = jax.jit(lambda a, b, p: matern52_gram(a, b, p, interpret=False))
        return fn.lower(x, x, _params(one_chip))

    names = ("jax_include_full_tracebacks_in_locations",
             "jax_hlo_source_file_canonicalization_regex")
    prev = {name: getattr(jax.config, name) for name in names}
    try:
        if stable:
            stable_source_locations(checkout)
        shallow = lower(0)
        jax.clear_caches()  # trace the kernel again, from the deeper caller
        deep = lower(3)
    finally:
        for name, value in prev.items():
            jax.config.update(name, value)
    same = _cache_key_ir(shallow) == _cache_key_ir(deep)
    assert same == stable
    payload = _mosaic_payload(shallow)
    assert b"kernel.py" in payload  # the locations are there
    assert (str(checkout).encode() in payload) != stable
