"""Candidate suggestion: the incremental BO decision engine of AMT (paper §4).

The engine is *stateful*: it reads observations from an
``ObservationStore`` (``repro.core.history``) and keeps two caches between
decisions so the per-decision cost is amortized, which is what makes the
paper's asynchronous slot-refill loop (§4.4) serve at fleet scale:

  * **GPHP samples** — slice-sampling (paper default, §4.2) is the dominant
    cost. ``BOConfig.refit_every`` re-samples only after that many *new*
    observations; between refits the cached draws are reused and only the
    posterior factors change.
  * **Cholesky factors** — one ``GPPosterior`` per GPHP sample is cached.
    A new observation is folded in by a rank-1 border append
    (``repro.core.gp.incremental``, O(S·n²)) instead of refactorizing at
    O(S·n³); ``alpha`` is recomputed each decision because the running
    standardization rescales every target.

One decision step (``suggest_batch``):

  1. Read the store's standardized snapshot (encoded X, zero-mean/unit-std y
     — paper §4.2); cold-start from a Sobol design below ``num_init`` (§2.1).
  2. Bring the cached posterior up to date (refit / rank-1 appends).
  3. Handle pending candidates (§4.4): "exclude" (paper-faithful — never
     re-propose), or fantasize them onto a scratch posterior via the same
     rank-1 append ("liar" / "kb", beyond-paper).
  4. For each of the k freed slots: optimize integrated EI over Sobol anchors
     + gradient refinement (§4.3), round-trip the winner through the search
     space, de-duplicate, then fantasize the interim pick so the remaining
     slots are filled from one pipeline pass instead of k full pipelines.

``suggest(history, pending)`` remains as a compatibility wrapper: it syncs a
private store by prefix-diffing the passed history (append-only callers get
the incremental path for free; anything else falls back to a full rebuild,
i.e. the seed's stateless behavior).

Both caches live in an ``EngineCache`` object the suggester owns by default;
in service mode (``repro.core.service``) the ``SelectionService`` owns it
instead — sibling jobs on the same search space adopt each other's GPHP
draws through a shared pool, and a factor arena bounds the total resident
Cholesky memory across jobs (eviction drops factors only; rebuilds are
RNG-free, so suggestions are invariant under eviction).

Shape bucketing keeps jit recompiles logarithmic in the number of
observations; growing into a larger bucket pads the cached factors with an
identity block rather than refactorizing.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gp import gp as gplib
from repro.core.gp import params as gpparams
from repro.core.gp.empirical_bayes import EmpiricalBayesConfig
from repro.core.gp.fit import map_gphps, mcmc_gphps
from repro.core.gp.incremental import (
    grow_posterior,
    posterior_append,
    posterior_append_block,
    posterior_delete,
    refresh_alpha,
)
from repro.core.gp.slice_sampler import (
    FAST_CONFIG,
    PAPER_CONFIG,
    SliceSamplerConfig,
)
from repro.core.gp.sparse import select_inducing
from repro.core import telemetry
from repro.core.history import ObservationStore, bucket_size
from repro.core.optimize_acq import (
    AcqOptConfig,
    MultiAcqSpec,
    MultiMetricHead,
    optimize_acquisition,
    optimize_acquisition_multi,
)
from repro.core.search_space import SearchSpace
from repro.core.sobol import SobolSequence

__all__ = [
    "BOConfig",
    "BOSuggester",
    "EngineCache",
    "RandomSuggester",
    "SobolSuggester",
]

Observation = Tuple[Mapping[str, Any], float]


@dataclasses.dataclass(frozen=True)
class BOConfig:
    """Configuration of the BO engine. Defaults are the paper's choices.

    Two backend knobs, deliberately independent:

    * ``backend`` — anchor-*scoring* backend, a convenience that overrides
      ``acq.backend``. ``"pallas"`` fuses cross-gram + cached-factor solve +
      EI/LCB into one kernel pass (``repro.kernels.acq_score``).
    * ``fit_backend`` — gram backend for GPHP fitting and posterior
      factorization (MCMC marginal-likelihood grams, refits, rank-1 appends).
      Kept separate so switching the scoring backend never perturbs the
      fitted posterior — ``backend="pallas"`` and ``backend="xla"`` engines
      walk bit-identical GPHP chains and differ only in how anchors are
      scored (the e2e invariance tests rely on this).

    ``fit_on_host`` places GPHP fitting (slice chain or MAP) on the host's
    CPU device, in float64 like the rest of the GP, whatever the default
    device is; the draws come back to the caller's default device. A chain
    is ~2400 serial log-density probes, each an O(n³) Cholesky: on a TPU
    v5e, which emulates float64, one probe at n = 512 took ~175 ms, so a
    paper-default refit took ~7 minutes against ~17 s on a CPU. The Matérn
    Pallas kernel would only be interpreted there, so ``fit_backend=
    "pallas"`` needs ``fit_on_host=False``.
    """

    num_init: int = 3  # Sobol initial design before the GP takes over
    gphp_method: str = "mcmc"  # "mcmc" (slice sampling) | "map" (empirical Bayes)
    slice_config: SliceSamplerConfig = PAPER_CONFIG
    eb_config: EmpiricalBayesConfig = EmpiricalBayesConfig()
    acq: AcqOptConfig = AcqOptConfig()
    pending_strategy: str = "exclude"  # "exclude" | "liar" | "kb" (beyond-paper)
    liar_value: float = 0.0  # standardized-space constant liar (0 = mean liar)
    dedupe_tol: float = 1e-6  # L∞ tolerance for duplicate candidates
    max_pending: int = 64  # static pad size for the pending buffer
    refit_every: int = 1  # re-sample GPHPs after this many new observations
    incremental: bool = True  # rank-1 posterior updates between refits
    backend: Optional[str] = None  # constructor shorthand: folded into
    # acq.backend and reset to None, so a later dataclasses.replace(acq=...)
    # is never stomped by a stale shorthand
    fit_backend: str = "xla"  # gram backend for GPHP fitting/factorization
    fit_on_host: bool = True  # GPHP fitting on the host CPU device
    num_scalarizations: int = 16  # Pareto mode: simplex weight draws/decision
    fantasy_block: bool = False  # fold the pending set with one rank-k
    # blocked append instead of k rank-1 borders ("liar" strategy only);
    # off by default to keep the fantasy fold bit-identical to PR 1
    posterior_backend: str = "exact"  # "exact" | "subset" (inducing rows,
    # core/gp/sparse.py) — "subset" caps the factor at max_inducing rows
    # once the refit boundary reaches n_switch; below that it is
    # bit-identical to "exact"
    n_switch: int = 2048  # store rows at a refit boundary before "subset"
    # actually switches away from the exact factorization
    max_inducing: int = 1024  # inducing rows selected at each refit boundary
    per_head_gphp: bool = False  # M>1 jobs: give every constraint/latency
    # head its own GPHP chain (and factor) instead of sharing the objective's
    # draws; default off — the shared-factor layout of PR 5
    cost_aware: bool = False  # EI-per-unit-cost: a log-cost head rides the
    # shared factor and EI is discounted by exp(-eta * zc(x)); off (the
    # default) is bit-identical to the cost-blind engine
    cost_cooling: float = 1.0  # eta scale for the cost discount; with a
    # capped budget ledger attached the effective eta decays linearly with
    # spend, so the cheap-first bias fades as the job closes on its budget

    def __post_init__(self):
        if self.backend is not None:
            if self.backend != self.acq.backend:
                object.__setattr__(
                    self, "acq", self.acq._replace(backend=self.backend)
                )
            object.__setattr__(self, "backend", None)
        if self.posterior_backend not in ("exact", "subset"):
            raise ValueError(
                f"unknown posterior_backend {self.posterior_backend!r} "
                "(expected 'exact' or 'subset')"
            )
        if self.fit_on_host and self.fit_backend == "pallas":
            raise ValueError(
                "fit_backend='pallas' needs fit_on_host=False: on the host "
                "the Matérn kernel would run in the Pallas interpreter"
            )
        if self.max_inducing < 2:
            raise ValueError("max_inducing must be at least 2")
        if self.cost_cooling < 0:
            raise ValueError("cost_cooling must be non-negative")

    def fast(self) -> "BOConfig":
        """Cheaper MCMC settings for many-seed benchmark sweeps."""
        return dataclasses.replace(self, slice_config=FAST_CONFIG)


class EngineCache:
    """The extractable cache block of the incremental BO engine.

    Holds everything a decision reuses between calls: the packed GPHP draws,
    the factorized ``GPPosterior`` covering the store prefix ``[0, n)``, and
    the refit-cadence accounting. A standalone ``BOSuggester`` owns a private
    instance; a ``SelectionService`` (``repro.core.service``) instead hands
    out instances wired to a shared **GPHP sample pool** (sibling jobs on the
    same search space adopt each other's draws instead of re-running MCMC)
    and registered in a **factor arena** (an LRU bound on total resident
    Cholesky/L⁻¹ memory — eviction calls ``drop_factors``, which is always
    safe: the factorization rebuilds from ``samples`` without consuming any
    RNG state, so suggestions are invariant under eviction).
    """

    def __init__(self, pool=None, arena=None, arena_key=None):
        self.samples: Optional[np.ndarray] = None  # packed (S, 3d+2) draws
        self.post = None  # GPPosterior for the live rows (see live_rows)
        self.n = 0  # observations folded into the cadence accounting
        self.obs_since_refit = 0
        self.token: Optional[int] = None  # id() of the store the cache maps
        self.pool = pool  # GPHPSamplePool shared by sibling jobs (or None)
        self.pool_version = -1  # pool.version last adopted/published
        self.arena = arena  # FactorArena bounding factor residency (or None)
        self.arena_key = arena_key
        self.store = None  # last bound ObservationStore (arena accounting)
        # --- subset posterior backend (core/gp/sparse.py) -----------------
        # store-row indices of the inducing set selected at the last refit
        # boundary, or None when the exact backend is live. inducing_n0 is
        # the store-row count at selection time: rows [inducing_n0, n) were
        # appended to the factor after the boundary.
        self.inducing_sel: Optional[np.ndarray] = None
        self.inducing_n0 = 0
        # --- per-head GPHP chains (BOConfig.per_head_gphp) ----------------
        self.head_samples: Optional[List[np.ndarray]] = None  # per extra head
        self.head_posts: Optional[list] = None  # per-head GPPosteriors
        self.head_n = 0  # store rows folded into the head factors
        self.head_alphas = None  # last shared-factor head alphas (accounting)

    # ------------------------------------------------------------ live rows
    def live_rows(self, n: int) -> np.ndarray:
        """Store-row indices the resident factor covers, in factor order:
        all of ``[0, n)`` on the exact backend, else the inducing set plus
        every row appended since the boundary."""
        if self.inducing_sel is None:
            return np.arange(n, dtype=np.int64)
        return np.concatenate(
            [self.inducing_sel, np.arange(self.inducing_n0, n, dtype=np.int64)]
        )

    # ------------------------------------------------------------ lifecycle
    def reset(self) -> None:
        self.samples = None
        self.post = None
        self.n = 0
        self.obs_since_refit = 0
        self.token = None
        self.pool_version = -1
        self.inducing_sel = None
        self.inducing_n0 = 0
        self.head_samples = None
        self.head_posts = None
        self.head_n = 0
        self.head_alphas = None

    def invalidate_factors(self) -> None:
        """Forget the factorization but keep draws + cadence (store rebind)."""
        self.post = None
        self.token = None
        self.inducing_sel = None
        self.inducing_n0 = 0
        self.head_posts = None
        self.head_alphas = None

    def drop_factors(self) -> None:
        """Arena eviction hook: release the O(S·n²) factor blocks (objective
        and per-head) plus the cached head alphas. The next decision rebuilds
        them from ``samples``/``head_samples`` (RNG-free, deterministic) —
        including the inducing-set selection, which is a pure function of the
        store prefix at the boundary."""
        self.post = None
        self.inducing_sel = None
        self.inducing_n0 = 0
        self.head_posts = None
        self.head_alphas = None

    def factor_nbytes(self) -> int:
        """Resident bytes of the factor blocks (what the arena budgets):
        the objective posterior (L, L⁻¹, alpha, x, mask), any per-head
        posteriors, and the cached multi-head alpha block."""
        total = 0
        blocks = [self.post, self.head_alphas]
        if self.head_posts:
            blocks.extend(self.head_posts)
        for block in blocks:
            if block is None:
                continue
            for leaf in jax.tree_util.tree_leaves(block):
                if hasattr(leaf, "nbytes"):
                    total += int(leaf.nbytes)
        return total

    def store_nbytes(self) -> int:
        """Resident bytes of the bound observation store (rows + pending
        buffers) — the un-evictable floor of the arena's end-to-end budget."""
        if self.store is None or not hasattr(self.store, "nbytes"):
            return 0
        return int(self.store.nbytes())

    def touched(self) -> None:
        """Mark this cache most-recently-used in its arena (if any)."""
        if self.arena is not None:
            self.arena.touch(self.arena_key, self)

    # ----------------------------------------------------------- wire image
    def snapshot(self, include_factors: bool = False) -> Dict[str, Any]:
        """Exact wire image of the cache block (versioned by the enclosing
        engine snapshot — see ``SelectionService.snapshot_job``).

        ``include_factors=False`` (default) ships only the GPHP draws and the
        cadence counters: the factor blocks are a deterministic function of
        draws + observation rows, so a restoring replica rehydrates them
        locally (the same RNG-free rebuild arena eviction uses) instead of
        paying O(S·n²) wire bytes. ``include_factors=True`` additionally
        ships the factorized posterior for hot hand-offs.
        """
        from repro.core.gp.serialize import array_to_wire, posterior_to_wire

        return {
            "samples": array_to_wire(self.samples),
            "n": self.n,
            "obs_since_refit": self.obs_since_refit,
            "pool_version": self.pool_version,
            "factors": posterior_to_wire(self.post)
            if include_factors and self.post is not None
            else None,
            # subset backend: the inducing set is replayable (select_inducing
            # is deterministic over the store prefix), but shipping it keeps
            # factor-bearing snapshots self-describing and lets a restore
            # resume the append path without recomputing the selection.
            "inducing_sel": array_to_wire(self.inducing_sel),
            "inducing_n0": self.inducing_n0,
            # per-head GPHP draws (factors rehydrate like the objective's)
            "head_samples": None
            if self.head_samples is None
            else [array_to_wire(s) for s in self.head_samples],
            "head_n": self.head_n,
        }

    def load_snapshot(self, snap: Mapping[str, Any]) -> None:
        """Install ``snapshot()`` output. Pool/arena wiring is left untouched
        (those belong to the hosting service, not the wire image); factors
        rehydrate lazily on the next decision unless the snapshot shipped
        them."""
        from repro.core.gp.serialize import array_from_wire, posterior_from_wire

        self.samples = array_from_wire(snap["samples"])
        self.n = int(snap["n"])
        self.obs_since_refit = int(snap["obs_since_refit"])
        self.pool_version = int(snap["pool_version"])
        factors = snap.get("factors")
        self.post = None if factors is None else posterior_from_wire(factors)
        self.token = None  # factors (if any) bind to whatever store comes next
        sel = array_from_wire(snap.get("inducing_sel"))
        self.inducing_sel = None if sel is None else sel.astype(np.int64)
        self.inducing_n0 = int(snap.get("inducing_n0", 0))
        hs = snap.get("head_samples")
        self.head_samples = (
            None if hs is None else [array_from_wire(s) for s in hs]
        )
        self.head_posts = None  # rehydrated lazily, like the objective factors
        self.head_n = int(snap.get("head_n", 0))
        self.head_alphas = None


class BOSuggester:
    """Stateful sequential/asynchronous Bayesian-optimization suggester
    (minimize). Bind an ``ObservationStore`` (``bind_store``) and call
    ``suggest_batch(k)``; or use the stateless ``suggest(history, pending)``
    compatibility API.

    Args:
        space: the ``SearchSpace`` candidates are drawn from.
        config: engine knobs (``BOConfig``; defaults are the paper's).
        seed: drives every random element — numpy RNG, JAX key, and the
            Sobol shift scramble. Recorded on the instance so an engine
            snapshot (``SelectionService.snapshot_job``) can reconstruct the
            suggester in a fresh process; two suggesters built with the same
            (space, config, seed) walk identical decision streams.
        store: optional ``ObservationStore`` to bind now (else ``bind_store``).
        cache: optional service-owned ``EngineCache`` (else a private one).

    ``state_dict()``/``load_state_dict()`` capture everything *drawn since
    construction* (chain state, RNG streams, cached GPHP draws, cadence), so
    construction-from-seed + ``load_state_dict`` reproduces a live engine
    exactly — the contract both Tuner checkpoints and engine snapshots rest
    on. Factors are never part of the state: they rehydrate via an RNG-free
    replay of the incremental construction (see ``_posterior_for``).
    """

    def __init__(
        self,
        space: SearchSpace,
        config: BOConfig = BOConfig(),
        seed: int = 0,
        store: Optional[ObservationStore] = None,
        cache: Optional[EngineCache] = None,
    ):
        self.space = space
        self.config = config
        # construction seed: recorded so an engine snapshot can rebuild this
        # suggester in a fresh process (the Sobol shift scramble is drawn at
        # construction and is not part of state_dict).
        self.seed = seed
        self._rng = np.random.default_rng(seed)  # invariant: fresh-rng -- constructor-seeded; the bit-generator state is checkpointed in state_dict and restored on replay
        self._key = jax.random.PRNGKey(seed)
        self._sobol_init = SobolSequence(space.encoded_dim, shift_rng=np.random.default_rng(seed))  # invariant: fresh-rng -- shift scramble is a pure function of the recorded construction seed; rebuilt identically from the snapshot
        self._anchor_gen = SobolSequence(space.encoded_dim)
        self._anchors = jnp.asarray(self._anchor_gen.next(config.acq.num_anchors))
        self._bounds = gpparams.default_bounds(
            space.encoded_dim, space.warpable_dims()
        )
        # persisted slice-chain state: warm-starts the next chain (paper runs
        # one chain per decision; warm chains amortize burn-in).
        self._chain_state: Optional[np.ndarray] = None
        # per-head chains (BOConfig.per_head_gphp): slot j warm-starts the
        # chain of extra head j+1
        self._head_chain_states: Dict[int, np.ndarray] = {}
        # did the last _posterior_for re-fit or adopt draws? (the per-head
        # factors re-fit at exactly the objective's boundaries)
        self._boundary_refit = False
        # --- incremental-engine caches -----------------------------------
        self._store: Optional[ObservationStore] = store
        if store is not None:
            self._check_multimetric_config(store)
        # in-service ASHA state (``repro.core.multifidelity``) — set by the
        # SelectionService when the job declares multi_fidelity. None (the
        # default) keeps every decision bit-identical to the exact path.
        self.multi_fidelity_state = None
        # budget ledger (``repro.core.budget``) — attached by the Tuner or
        # SelectionService when the job declares max_cost or cost_aware.
        # None (the default) keeps state_dict byte-identical to cost-off.
        self.budget_ledger = None
        self._wrapper_store: Optional[ObservationStore] = None
        self._wrapper_fps: List[Tuple[float, bytes]] = []
        # the cache block is an object of its own so a SelectionService can
        # own it (shared GPHP pool + arena-bounded factors) and hand it out.
        self.cache = cache if cache is not None else EngineCache()

    # ------------------------------------------------- cache compat aliases
    @property
    def _cached_samples(self):
        return self.cache.samples

    @property
    def _cached_post(self):
        return self.cache.post

    # ------------------------------------------------------------------ rng
    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    # ----------------------------------------------------------- store glue
    def _check_multimetric_config(self, store: ObservationStore) -> None:
        """Reject config/store combinations the multi-metric decision path
        cannot serve — at bind time, not after the cold-start trials have
        already spent their budget."""
        ms = getattr(store, "metrics", None)
        if ms is not None and ms.num_metrics > 1 and self.config.acq.acq != "ei":
            raise ValueError(
                "multi-metric jobs support acq='ei' only (constrained EI / "
                f"random-scalarization EI), got {self.config.acq.acq!r}"
            )
        if self.config.cost_aware:
            if ms is not None and ms.num_metrics > 1:
                raise ValueError(
                    "cost_aware jobs are single-metric (the log-cost head "
                    "rides the objective factor; M > 1 stores already spend "
                    "the extra head slots on metrics)"
                )
            if self.config.acq.acq != "ei":
                raise ValueError(
                    "cost_aware jobs support acq='ei' only (EI-per-unit-"
                    f"cost), got {self.config.acq.acq!r}"
                )

    def bind_store(self, store: ObservationStore) -> None:
        """Attach the engine to a live observation store (the Tuner does this
        at construction and after restore). Cached GPHP samples survive a
        rebind — the cadence state may have been checkpoint-restored — but
        the factorization is rebuilt lazily against the new store."""
        self._check_multimetric_config(store)
        self._store = store
        self.cache.invalidate_factors()

    def attach_cache(self, cache: EngineCache) -> None:
        """Swap in a service-owned cache block (pool/arena wired). Any draws
        already cached privately carry over so attaching is never a regression
        for a warm engine."""
        if cache.samples is None and self.cache.samples is not None:
            cache.samples = self.cache.samples
            cache.n = self.cache.n
            cache.obs_since_refit = self.cache.obs_since_refit
            cache.token = self.cache.token
        self.cache = cache

    def reset_cache(self) -> None:
        self.cache.reset()

    def _sync_wrapper_store(self, history: Sequence[Observation]) -> ObservationStore:
        """Mirror a caller-owned history list into a private store. Append-only
        callers hit the incremental path. Two rewrite shapes stay incremental
        too (history *corrections*, the ROADMAP rank-1-downdate item):

          * objective values rewritten at unchanged inputs — the Cholesky
            factor depends only on X, so the cached factorization survives
            and only the store targets are rewritten (alpha refreshes every
            decision anyway);
          * exactly one entry deleted — the store drops the row and the
            cached factor takes a rank-1 *downdate* (``posterior_delete``,
            O(S·n²)) instead of a from-scratch refit.

        Anything else falls back to a fresh store + full refit (the seed's
        stateless semantics)."""
        fps: List[Tuple[float, bytes]] = []
        entries: List[Tuple[np.ndarray, float]] = []
        for cfg_, y in history:
            x = self.space.encode(cfg_)
            entries.append((x, float(y)))
            fps.append((float(y), x.tobytes()))
        fresh = self._wrapper_store is None
        if not fresh and fps[: len(self._wrapper_fps)] == self._wrapper_fps:
            tail = entries[len(self._wrapper_fps):]
        else:
            tail = None if fresh else self._try_incremental_rewrite(fps, entries)
            if tail is None:
                if not fresh:  # unrecognized rewrite: cached state is stale
                    self.reset_cache()
                self._wrapper_store = ObservationStore(self.space)
                tail = entries
        for x, y in tail:
            self._wrapper_store.push_encoded(x, y)
        self._wrapper_fps = fps
        return self._wrapper_store

    def _try_incremental_rewrite(
        self,
        fps: List[Tuple[float, bytes]],
        entries: List[Tuple[np.ndarray, float]],
    ) -> Optional[List[Tuple[np.ndarray, float]]]:
        """Recognize a correction-shaped history rewrite (see
        ``_sync_wrapper_store``); returns the append tail on success, None to
        fall back to the stateless rebuild. Only histories whose rows all
        reached the store (every objective finite) are eligible — dropped
        rows would desynchronize fps indices from store rows."""
        import math

        old = self._wrapper_fps
        if any(not math.isfinite(y) for y, _ in old) or any(
            not math.isfinite(y) for y, _ in fps
        ):
            return None
        # --- objective-only rewrite: same inputs, some targets changed ------
        if len(fps) >= len(old) and all(
            fps[i][1] == old[i][1] for i in range(len(old))
        ):
            for i in range(len(old)):
                if fps[i][0] != old[i][0]:
                    self._wrapper_store.rewrite_own_y(i, fps[i][0])
            return entries[len(old):]
        # --- single deletion: old == new with one row removed ---------------
        cache = self.cache
        if (
            len(fps) >= len(old) - 1
            and cache.post is not None
            and cache.token in (None, id(self._wrapper_store))  # invariant: id-key -- within-process factor-cache identity check only; the token is never serialized and a fresh process rebuilds the cache from scratch
            and cache.n == len(old)
            # subset backend: store row i is not factor row i once the
            # inducing set is live, so the rank-1 downdate does not apply —
            # fall back to the stateless rebuild.
            and cache.inducing_sel is None
        ):
            for i in range(len(old)):
                if old[:i] == fps[:i] and old[i + 1 :] == fps[i : len(old) - 1]:
                    self._wrapper_store.delete_own(i)
                    cache.post = posterior_delete(cache.post, i)
                    cache.n -= 1
                    return entries[len(old) - 1 :]
        return None

    # ------------------------------------------------------------- main api
    def suggest(
        self,
        history: Sequence[Observation],
        pending: Sequence[Mapping[str, Any]] = (),
    ) -> Dict[str, Any]:
        """Compatibility wrapper: one decision from an explicit history."""
        store = self._sync_wrapper_store(history)
        pend_np = (
            self.space.encode_batch(list(pending))
            if pending
            else np.zeros((0, self.space.encoded_dim))
        )
        return self._decide(store, 1, pend_np)[0]

    def suggest_batch(self, k: int) -> List[Dict[str, Any]]:
        """Fill k freed slots in one engine pass (batched slot refill)."""
        if self._store is None:
            raise RuntimeError("suggest_batch requires a bound ObservationStore")
        with telemetry.span("suggest.encode"):
            pend_np = self._store.pending_encoded()
        return self._decide(self._store, k, pend_np)

    # ------------------------------------------------------------ decisions
    def _decision_posterior(
        self, store: ObservationStore, x_all: np.ndarray, y_std: np.ndarray
    ):
        """The decision's posterior: the cached factor (refit, adopted or
        appended as the cadence says) with alpha refreshed on the live rows'
        standardized targets ``y_std``. Returns (post, live rows in store
        order, the padded targets). The span covers the dispatch of the
        refresh, not its device work."""
        n = store.num_observations
        with telemetry.span("suggest.posterior", n=n):
            post = self._posterior_for(store, x_all, y_std)
            rows = self.cache.live_rows(n)  # factor rows, in store order
            y_live = np.zeros(post.x_train.shape[0])
            y_live[: len(rows)] = y_std[rows]
            post = refresh_alpha(post, jnp.asarray(y_live))
        self.cache.post = post
        return post, rows, y_live

    def _pick(
        self, slot, work, pend_buf, pend_mask, x_all, pend_np, picks, *,
        y_best=None, head=None, spec=None,
    ):
        """One slot of a batched refill: optimize the acquisition (the
        multi-head program when ``spec`` is given), read its candidates to
        the host, and return (config, encoded vector) of the best that
        round-trips to a point not yet seen, else a quasi-random one.

        ``suggest.acq_opt`` ends once the candidates are on the host, so it
        holds the device's work; ``suggest.dedup`` is the host loop. The
        counters ``acq.refine.cached_inverse`` and ``acq.refine.solve`` say
        whether the dispatch's refinement reads a cached L⁻¹ for every
        posterior it predicts through, or re-solves some factor at every
        step (per-head factors carry no L⁻¹)."""
        cfg = self.config
        space = self.space
        posts = (work,) + (head.head_posts if head is not None else ())
        telemetry.count(
            "acq.refine.cached_inverse"
            if all(p.chol_inv is not None for p in posts)
            else "acq.refine.solve"
        )
        with telemetry.span(
            "suggest.acq_opt", backend=cfg.acq.backend, slot=slot
        ):
            if spec is None:
                cands, _ = optimize_acquisition(
                    work,
                    self._anchors,
                    y_best,
                    jnp.asarray(pend_buf),
                    jnp.asarray(pend_mask),
                    self._next_key(),
                    cfg.acq,
                )
            else:
                cands, _ = optimize_acquisition_multi(
                    work,
                    head,
                    self._anchors,
                    jnp.asarray(pend_buf),
                    jnp.asarray(pend_mask),
                    self._next_key(),
                    cfg.acq,
                    spec,
                )
            cands = np.asarray(cands)
        with telemetry.span("suggest.dedup", slot=slot):
            seen = self._seen_matrix(x_all, pend_np, picks)
            for cand in cands:
                snapped = space.round_trip(cand)
                if len(seen) == 0 or np.min(
                    np.max(np.abs(seen - snapped[None, :]), axis=1)
                ) > cfg.dedupe_tol:
                    return space.decode(snapped), snapped
            return self._quasi_random(seen)

    def _decide(
        self, store: ObservationStore, k: int, pend_np: np.ndarray
    ) -> List[Dict[str, Any]]:
        with telemetry.span(
            "suggest.decide", n=store.num_observations, k=k
        ):
            return self._decide_impl(store, k, pend_np)

    def _decide_impl(
        self, store: ObservationStore, k: int, pend_np: np.ndarray
    ) -> List[Dict[str, Any]]:
        cfg = self.config
        space = self.space
        n = store.num_observations
        picks: List[np.ndarray] = []
        out: List[Dict[str, Any]] = []

        if n < max(2, cfg.num_init):
            x_seen = store.x_rows(0, n)
            for _ in range(k):
                config, vec = self._quasi_random(
                    self._seen_matrix(x_seen, pend_np, picks)
                )
                picks.append(vec)
                out.append(config)
            return out

        ms = getattr(store, "metrics", None)
        if ms is not None and ms.num_metrics > 1:
            # multi-metric jobs branch off *after* the shared cold start; the
            # M=1 declaration never reaches here (bit-identical single path).
            return self._decide_multi(store, k, pend_np, ms)

        mf = self.multi_fidelity_state
        if cfg.cost_aware and mf is not None:
            raise ValueError(
                "cost_aware jobs do not support multi_fidelity (the rung "
                "heads already own the extra head slots)"
            )
        if mf is not None and mf.num_active_rungs() > 0:
            # multi-fidelity jobs score (x, r) jointly once rung tables hold
            # data; with empty tables (or multi_fidelity off) the exact
            # single-metric path below is untouched.
            return self._decide_rungs(store, k, pend_np, mf)

        if cfg.cost_aware:
            costs = store.own_costs()
            n_fin = sum(
                1 for c in costs
                if c is not None and math.isfinite(c) and c > 0.0
            )
            if n_fin >= 2:
                # the cost head needs two finite costs before its z-scoring
                # is meaningful; below that the decision falls through to the
                # exact cost-blind path (bit-identical — same RNG stream).
                return self._decide_cost(store, k, pend_np, costs)

        x_all, y_std, _, _ = store.standardized()
        post, rows, y_live = self._decision_posterior(store, x_all, y_std)
        n_live = len(rows)
        y_best = jnp.asarray(float(y_std.min()))  # best *real* observation

        # --- pending (§4.4) + scratch posterior for fantasies ---------------
        d = space.encoded_dim
        pend_buf = np.zeros((cfg.max_pending, d))
        pend_mask = np.zeros(cfg.max_pending, dtype=bool)
        n_excl = 0
        work = post
        y_work = list(y_live[:n_live])
        if cfg.pending_strategy in ("liar", "kb") and len(pend_np) > 0:
            if (
                cfg.fantasy_block
                and cfg.pending_strategy == "liar"
                and len(pend_np) > 1
            ):
                # rank-k blocked border: one O(k·n²) solve instead of k
                # sequential rank-1 borders (valid for the constant liar —
                # fantasy values don't depend on earlier fantasies).
                work, y_work = self._fantasy_append_block(work, y_work, pend_np)
            else:
                for xp in pend_np:
                    work, y_work = self._fantasy_append(work, y_work, xp)
        elif len(pend_np) > 0:
            n_excl = min(len(pend_np), cfg.max_pending)
            pend_buf[:n_excl] = pend_np[:n_excl]
            pend_mask[:n_excl] = True

        # --- batched refill: one pipeline pass fills all k slots -------------
        for slot in range(k):
            config, vec = self._pick(
                slot, work, pend_buf, pend_mask, x_all, pend_np, picks,
                y_best=y_best,
            )
            out.append(config)
            picks.append(vec)
            if slot + 1 < k:
                if cfg.pending_strategy in ("liar", "kb"):
                    work, y_work = self._fantasy_append(work, y_work, vec)
                elif n_excl < cfg.max_pending:
                    pend_buf[n_excl] = vec
                    pend_mask[n_excl] = True
                    n_excl += 1
        self.cache.touched()  # LRU bump + arena budget enforcement
        return out

    # ------------------------------------------------- multi-metric decisions
    def _decide_multi(
        self, store: ObservationStore, k: int, pend_np: np.ndarray, ms
    ) -> List[Dict[str, Any]]:
        """One batched decision for an M>1 job (``repro.core.multimetric``).

        The objective head (metric column 0) drives the exact single-metric
        machinery — GPHP fitting, the cached factor, rank-1 appends, the
        refit cadence — so the shared-factor invariants (snapshots, arena
        eviction, pool adoption) are untouched. The extra heads cost M−1
        triangular solves against that cached factor per decision
        (``solve_head_alphas``) plus one matvec per head inside scoring."""
        from repro.core.gp.multi import solve_head_alphas

        cfg = self.config
        space = self.space
        if cfg.acq.acq != "ei":
            raise ValueError(
                "multi-metric jobs support acq='ei' only (constrained EI / "
                f"random-scalarization EI), got {cfg.acq.acq!r}"
            )
        n = store.num_observations
        m_all = ms.num_metrics
        num_con = ms.num_constraints
        num_obj = ms.num_objectives

        x_all, ystd, means, scales = store.standardized_metrics()
        post, rows, _ = self._decision_posterior(
            store, x_all, np.ascontiguousarray(ystd[:, 0])
        )
        n_live = len(rows)
        size = post.x_train.shape[0]

        y_heads = np.zeros((m_all, size))
        y_heads[:, :n_live] = ystd[rows].T
        if cfg.per_head_gphp:
            # every extra head runs its own GPHP chain + factor; the shared
            # (S, M, n) alpha block is not built (head 0 scores through the
            # objective posterior directly).
            head_posts = self._head_posteriors_for(store, post, y_heads, n)
            alphas = jnp.asarray(post.alpha)[:, None, :]
            self.cache.head_alphas = None
        else:
            head_posts = ()
            alphas = solve_head_alphas(post, jnp.asarray(y_heads))
            self.cache.head_alphas = alphas  # arena accounting (factor_nbytes)

        # constraint thresholds + feasibility in standardized space
        t_signed = ms.signed_thresholds()  # (C,) raw signed bounds
        t_std = (t_signed - means[m_all - num_con :]) / scales[m_all - num_con :]
        raw = store.metric_matrix()  # (n, M) signed raw own rows
        if num_con:
            feas_rows = np.all(
                raw[:, m_all - num_con :] <= t_signed[None, :], axis=1
            )
        else:
            feas_rows = np.ones(len(raw), dtype=bool)
        has_feasible = bool(feas_rows.any())

        spec = MultiAcqSpec(
            mode=ms.mode, num_objectives=num_obj, num_constraints=num_con
        )
        if spec.mode == "constrained":
            y_best = float(ystd[feas_rows, 0].min()) if has_feasible else 0.0
            weights = np.zeros((0, num_obj))
            y_best_w = np.zeros((0,))
        else:
            # ParEGO-style random scalarizations: Dirichlet(1) simplex draws
            # from the engine RNG (checkpointed — restored jobs redraw the
            # exact weights an uninterrupted engine would have).
            w_draws = cfg.num_scalarizations
            g = -np.log1p(-self._rng.random((w_draws, num_obj)))
            weights = g / g.sum(axis=1, keepdims=True)
            rows = feas_rows if has_feasible else np.ones(len(raw), bool)
            sc = ystd[:n][rows][:, :num_obj] @ weights.T  # (n_r, W)
            y_best_w = sc.min(axis=0)
            y_best = 0.0

        def make_head(alphas_now, posts_now):
            return MultiMetricHead(
                alphas=alphas_now,
                t_std=jnp.asarray(t_std),
                y_best=jnp.asarray(y_best),
                has_feasible=jnp.asarray(has_feasible),
                weights=jnp.asarray(weights),
                y_best_w=jnp.asarray(y_best_w),
                head_posts=tuple(posts_now),
            )

        def refold_head(work_now, yh_now, heads_now):
            """Rebuild the MultiMetricHead after a fantasy fold."""
            if heads_now:
                return make_head(
                    jnp.asarray(work_now.alpha)[:, None, :], heads_now
                )
            return make_head(
                solve_head_alphas(
                    work_now, jnp.asarray(self._pad_heads(yh_now, work_now))
                ),
                (),
            )

        # --- pending (§4.4) + scratch posterior for fantasies ---------------
        d = space.encoded_dim
        pend_buf = np.zeros((cfg.max_pending, d))
        pend_mask = np.zeros(cfg.max_pending, dtype=bool)
        n_excl = 0
        work = post
        head_work = list(head_posts)  # per-head scratch (empty in shared mode)
        head = make_head(alphas, head_work)
        yh_work = [list(y_heads[j, :n_live]) for j in range(m_all)]
        if cfg.pending_strategy in ("liar", "kb") and len(pend_np) > 0:
            for xp in pend_np:
                work, yh_work, head_work = self._fantasy_append_multi(
                    work, yh_work, xp, head_work
                )
            head = refold_head(work, yh_work, head_work)
        elif len(pend_np) > 0:
            n_excl = min(len(pend_np), cfg.max_pending)
            pend_buf[:n_excl] = pend_np[:n_excl]
            pend_mask[:n_excl] = True

        picks: List[np.ndarray] = []
        out: List[Dict[str, Any]] = []
        for slot in range(k):
            config, vec = self._pick(
                slot, work, pend_buf, pend_mask, x_all, pend_np, picks,
                head=head, spec=spec,
            )
            out.append(config)
            picks.append(vec)
            if slot + 1 < k:
                if cfg.pending_strategy in ("liar", "kb"):
                    work, yh_work, head_work = self._fantasy_append_multi(
                        work, yh_work, vec, head_work
                    )
                    head = refold_head(work, yh_work, head_work)
                elif n_excl < cfg.max_pending:
                    pend_buf[n_excl] = vec
                    pend_mask[n_excl] = True
                    n_excl += 1
        self.cache.touched()  # LRU bump + arena budget enforcement
        return out

    # ----------------------------------------------- multi-fidelity decisions
    def _decide_rungs(
        self, store: ObservationStore, k: int, pend_np: np.ndarray, mf
    ) -> List[Dict[str, Any]]:
        """One batched decision for a multi-fidelity job whose rung tables
        hold data: the f(x, r) posterior of ``repro.core.gp.per_resource``.

        The objective head (final/cummin value) drives the exact
        single-metric machinery — GPHP chain, cached factor, rank-1 appends,
        refit cadence — untouched; each active rung adds one alpha solve
        against that factor per decision plus one matvec inside scoring
        (the shape of the multi-metric heads). Head targets are a pure
        function of (store rows + keys, rung tables), so every
        replay-rehydration invariant (arena eviction, snapshot restore,
        oplog failover) holds for the rung heads for free."""
        from repro.core.gp.multi import solve_head_alphas
        from repro.core.gp.per_resource import (
            rung_head_targets,
            rung_head_weights,
        )

        cfg = self.config
        space = self.space
        if cfg.acq.acq != "ei":
            raise ValueError(
                "multi-fidelity jobs support acq='ei' only (rung-weighted "
                f"EI), got {cfg.acq.acq!r}"
            )
        n = store.num_observations
        num_rungs = mf.num_active_rungs()
        m_all = 1 + num_rungs

        x_all, y_std, _, _ = store.standardized()
        post, rows, _ = self._decision_posterior(store, x_all, y_std)
        n_live = len(rows)
        size = post.x_train.shape[0]

        # (R, n) standardized rung-head targets; rows without a rung-k value
        # impute their final objective (dense columns — no per-head masks).
        rung_t = rung_head_targets(store, mf.rungs, num_rungs, y_std)
        y_heads = np.zeros((m_all, size))
        y_heads[0, :n_live] = y_std[rows]
        y_heads[1:, :n_live] = rung_t[:, rows]
        alphas = solve_head_alphas(post, jnp.asarray(y_heads))
        self.cache.head_alphas = alphas  # arena accounting (factor_nbytes)

        weights = rung_head_weights(mf.rung_grid, num_rungs)  # (1, R+1)
        # per-head incumbents: each head's EI improves on its own best
        y_best = float(y_std[:n].min())
        y_best_w = np.concatenate(([y_best], rung_t.min(axis=1)))
        spec = MultiAcqSpec(
            mode="rungs", num_objectives=m_all, num_constraints=0
        )

        def make_head(alphas_now):
            return MultiMetricHead(
                alphas=alphas_now,
                t_std=jnp.zeros((0,)),
                y_best=jnp.asarray(y_best),
                has_feasible=jnp.asarray(True),
                weights=jnp.asarray(weights),
                y_best_w=jnp.asarray(y_best_w),
                head_posts=(),
            )

        def refold_head(work_now, yh_now):
            """Rebuild the head block after a fantasy fold."""
            return make_head(
                solve_head_alphas(
                    work_now, jnp.asarray(self._pad_heads(yh_now, work_now))
                )
            )

        # --- pending (§4.4) + scratch posterior for fantasies ---------------
        d = space.encoded_dim
        pend_buf = np.zeros((cfg.max_pending, d))
        pend_mask = np.zeros(cfg.max_pending, dtype=bool)
        n_excl = 0
        work = post
        head = make_head(alphas)
        yh_work = [list(y_heads[j, :n_live]) for j in range(m_all)]
        if cfg.pending_strategy in ("liar", "kb") and len(pend_np) > 0:
            for xp in pend_np:
                work, yh_work, _ = self._fantasy_append_multi(
                    work, yh_work, xp, []
                )
            head = refold_head(work, yh_work)
        elif len(pend_np) > 0:
            n_excl = min(len(pend_np), cfg.max_pending)
            pend_buf[:n_excl] = pend_np[:n_excl]
            pend_mask[:n_excl] = True

        picks: List[np.ndarray] = []
        out: List[Dict[str, Any]] = []
        for slot in range(k):
            config, vec = self._pick(
                slot, work, pend_buf, pend_mask, x_all, pend_np, picks,
                head=head, spec=spec,
            )
            out.append(config)
            picks.append(vec)
            if slot + 1 < k:
                if cfg.pending_strategy in ("liar", "kb"):
                    work, yh_work, _ = self._fantasy_append_multi(
                        work, yh_work, vec, []
                    )
                    head = refold_head(work, yh_work)
                elif n_excl < cfg.max_pending:
                    pend_buf[n_excl] = vec
                    pend_mask[n_excl] = True
                    n_excl += 1
        self.cache.touched()  # LRU bump + arena budget enforcement
        return out

    # ------------------------------------------------- cost-aware decisions
    def _decide_cost(
        self,
        store: ObservationStore,
        k: int,
        pend_np: np.ndarray,
        costs: List[Optional[float]],
    ) -> List[Dict[str, Any]]:
        """One batched decision under EI-per-unit-cost (``BOConfig.
        cost_aware``): a GP head over *standardized log-cost* rides the
        shared Cholesky factor (one extra alpha solve per decision, the
        multi-metric/rung layout), and anchors score

            EIpu(x) = EI(x) · exp(−η · ẑc(x))

        where ẑc is the posterior mean of the log-cost head and η =
        ``cost_cooling`` · max(0, 1 − spent/max_cost) when a capped budget
        ledger is attached (constant ``cost_cooling`` otherwise) — the
        cheap-first bias cools as the budget spends, so late decisions
        converge to plain EI near the incumbent. Because ẑc is standardized,
        uniform observed costs give ẑc ≡ 0 and EIpu == EI exactly.

        Own rows without a recorded cost — and warm-start parent rows, which
        never carry one — impute target 0 (the head mean): they exert no
        discount pressure in either direction. Head targets are a pure
        function of store rows, so every replay-rehydration invariant
        (arena eviction, snapshot restore, oplog failover) holds for the
        cost head for free."""
        from repro.core.gp.multi import solve_head_alphas

        cfg = self.config
        space = self.space
        if cfg.acq.acq != "ei":
            raise ValueError(
                f"cost_aware jobs support acq='ei' only, got {cfg.acq.acq!r}"
            )
        n = store.num_observations
        m_all = 2  # objective head + log-cost head

        x_all, y_std, _, _ = store.standardized()
        post, rows, _ = self._decision_posterior(store, x_all, y_std)
        n_live = len(rows)
        size = post.x_train.shape[0]

        # standardized log-cost targets over the full store prefix
        zc = np.zeros(n)
        npar = n - len(costs)
        fin = np.asarray(
            [c is not None and math.isfinite(c) and c > 0.0 for c in costs],
            dtype=bool,
        )
        logs = np.asarray(
            [math.log(c) if ok else 0.0 for c, ok in zip(costs, fin)]
        )
        mean = float(logs[fin].mean())
        std = float(logs[fin].std())
        scale = std if std > 1e-12 else 1.0
        zc[npar:][fin] = (logs[fin] - mean) / scale

        y_heads = np.zeros((m_all, size))
        y_heads[0, :n_live] = y_std[rows]
        y_heads[1, :n_live] = zc[rows]
        alphas = solve_head_alphas(post, jnp.asarray(y_heads))
        self.cache.head_alphas = alphas  # arena accounting (factor_nbytes)

        ledger = self.budget_ledger
        eta = cfg.cost_cooling
        if ledger is not None and ledger.max_cost is not None:
            eta *= max(0.0, 1.0 - ledger.spent / ledger.max_cost)
        weights = np.asarray([[eta]])  # (1, 1): eta travels the weights slot
        y_best = float(y_std[:n].min())
        y_best_w = np.zeros((1,))  # unused in cost mode (EI on head 0 only)
        spec = MultiAcqSpec(
            mode="cost", num_objectives=m_all, num_constraints=0
        )

        def make_head(alphas_now):
            return MultiMetricHead(
                alphas=alphas_now,
                t_std=jnp.zeros((0,)),
                y_best=jnp.asarray(y_best),
                has_feasible=jnp.asarray(True),
                weights=jnp.asarray(weights),
                y_best_w=jnp.asarray(y_best_w),
                head_posts=(),
            )

        def refold_head(work_now, yh_now):
            """Rebuild the head block after a fantasy fold."""
            return make_head(
                solve_head_alphas(
                    work_now, jnp.asarray(self._pad_heads(yh_now, work_now))
                )
            )

        # --- pending (§4.4) + scratch posterior for fantasies ---------------
        d = space.encoded_dim
        pend_buf = np.zeros((cfg.max_pending, d))
        pend_mask = np.zeros(cfg.max_pending, dtype=bool)
        n_excl = 0
        work = post
        head = make_head(alphas)
        yh_work = [list(y_heads[j, :n_live]) for j in range(m_all)]
        if cfg.pending_strategy in ("liar", "kb") and len(pend_np) > 0:
            for xp in pend_np:
                work, yh_work, _ = self._fantasy_append_multi(
                    work, yh_work, xp, []
                )
            head = refold_head(work, yh_work)
        elif len(pend_np) > 0:
            n_excl = min(len(pend_np), cfg.max_pending)
            pend_buf[:n_excl] = pend_np[:n_excl]
            pend_mask[:n_excl] = True

        picks: List[np.ndarray] = []
        out: List[Dict[str, Any]] = []
        for slot in range(k):
            config, vec = self._pick(
                slot, work, pend_buf, pend_mask, x_all, pend_np, picks,
                head=head, spec=spec,
            )
            out.append(config)
            picks.append(vec)
            if slot + 1 < k:
                if cfg.pending_strategy in ("liar", "kb"):
                    work, yh_work, _ = self._fantasy_append_multi(
                        work, yh_work, vec, []
                    )
                    head = refold_head(work, yh_work)
                elif n_excl < cfg.max_pending:
                    pend_buf[n_excl] = vec
                    pend_mask[n_excl] = True
                    n_excl += 1
        self.cache.touched()  # LRU bump + arena budget enforcement
        return out

    @staticmethod
    def _pad_heads(yh_work: List[List[float]], work) -> np.ndarray:
        """Stack per-head target lists into the (M, bucket) padded block."""
        size = work.x_train.shape[0]
        out = np.zeros((len(yh_work), size))
        for j, col in enumerate(yh_work):
            out[j, : len(col)] = col
        return out

    def _fantasy_append_multi(
        self,
        work,
        yh_work: List[List[float]],
        x_vec: np.ndarray,
        head_work: Optional[list] = None,
    ):
        """Multi-head fantasy fold: append the input once per resident factor
        (the shared factor, plus each per-head factor when
        ``per_head_gphp`` is on), extend every head's target list with its
        fantasy value (constant liar, or per-head kriging-believer means)."""
        cfg = self.config
        head_work = list(head_work) if head_work else []
        xq = jnp.asarray(x_vec)
        if cfg.pending_strategy == "kb":
            if head_work:
                # per-head kriging believer: each head's own posterior mean
                mu0, _ = gplib.predict(
                    work, xq[None, :], backend=cfg.fit_backend
                )
                vals = [float(jnp.mean(mu0))]
                for hp in head_work:
                    muh, _ = gplib.predict(
                        hp, xq[None, :], backend=cfg.fit_backend
                    )
                    vals.append(float(jnp.mean(muh)))
            else:
                from repro.core.gp.multi import (
                    MultiOutputPosterior,
                    predict_heads,
                    solve_head_alphas,
                )

                alphas_now = solve_head_alphas(
                    work, jnp.asarray(self._pad_heads(yh_work, work))
                )
                mu, _ = predict_heads(
                    MultiOutputPosterior(work, alphas_now),
                    xq[None, :],
                    backend=cfg.fit_backend,
                )  # (S, M, 1)
                vals = [
                    float(v) for v in np.asarray(jnp.mean(mu, axis=0))[:, 0]
                ]
        else:
            vals = [cfg.liar_value] * len(yh_work)
        live = len(yh_work[0])
        if live >= work.x_train.shape[0]:
            work = grow_posterior(work, bucket_size(live + 1))
        work = posterior_append(work, xq, backend=cfg.fit_backend)
        yh_work = [col + [v] for col, v in zip(yh_work, vals)]
        y_pad = np.zeros(work.x_train.shape[0])
        y_pad[: len(yh_work[0])] = yh_work[0]
        work = refresh_alpha(work, jnp.asarray(y_pad))
        if head_work:
            refolded = []
            for j, hp in enumerate(head_work):
                if live >= hp.x_train.shape[0]:
                    hp = grow_posterior(hp, bucket_size(live + 1))
                hp = posterior_append(hp, xq, backend=cfg.fit_backend)
                col = yh_work[j + 1]
                yj = np.zeros(hp.x_train.shape[0])
                yj[: len(col)] = col
                refolded.append(refresh_alpha(hp, jnp.asarray(yj)))
            head_work = refolded
        return work, yh_work, head_work

    # ------------------------------------------------------ posterior cache
    def _posterior_for(
        self, store: ObservationStore, x_all: np.ndarray, y_std: np.ndarray
    ):
        """Return a posterior covering the store's n rows, via (in order of
        preference) the cached factors + rank-1 appends, pooled sibling GPHP
        draws (service mode), a refactorization under cached draws, or a full
        GPHP refit."""
        cfg = self.config
        cache = self.cache
        pool = cache.pool
        n = x_all.shape[0]
        d = self.space.encoded_dim
        token = id(store)  # invariant: id-key -- within-process factor-cache identity check only; never serialized, rebuilt per process
        cache.store = store  # arena end-to-end accounting
        self._boundary_refit = False  # did this decision re-fit/adopt draws?

        samples_valid = (
            cfg.incremental
            and cache.samples is not None
            and cache.token in (None, token)
            and cache.n <= n
        )
        post_valid = samples_valid and cache.post is not None
        acct = cache.n if samples_valid else 0
        new_obs = n - acct
        resample = not samples_valid or (
            new_obs > 0 and cache.obs_since_refit + new_obs >= cfg.refit_every
        )

        expected_s = (
            1 if cfg.gphp_method == "map" else cfg.slice_config.num_kept
        )
        if (
            resample
            and cfg.incremental
            and pool is not None
            and pool.samples is not None
            and pool.version > cache.pool_version
            # a sibling fitted with a different GPHP budget: its draw count
            # would silently replace this job's configured fidelity (and
            # churn jit shape buckets) — only adopt shape-compatible draws.
            and pool.samples.shape[0] == expected_s
        ):
            # A sibling job published fresher draws since our last sync:
            # adopt them instead of re-running MCMC. This is the pool-level
            # cadence — across a group of N sibling jobs roughly one MCMC fit
            # happens per ``refit_every`` *group* observations instead of one
            # per job, and a cold job joining the group skips burn-in
            # entirely. Draws are hyperparameter posteriors of a sibling's
            # data on the same space (typically overlapping via sibling
            # warm-start), so this is an approximation; disable with
            # ``ServiceConfig(share_gphp=False)`` for bit-faithful chains.
            cache.samples = np.array(pool.samples)
            cache.pool_version = pool.version
            cache.obs_since_refit = 0
            if self._chain_state is None and pool.chain_state is not None:
                self._chain_state = np.array(pool.chain_state)
            pool.adoptions += 1
            telemetry.count("suggest.gphp.adopt")
            resample = False
            post_valid = False  # factors (if any) describe the old draws
            new_obs = 0  # the adopted draws cover all current rows
            acct = n  # adoption refactorizes at n: the new factor boundary
            self._boundary_refit = True

        if pool is not None:
            pool.decisions += 1

        if resample:
            self._boundary_refit = True
            telemetry.count("suggest.gphp.refit")
            rows = self._boundary_rows(x_all, n)
            xj, yj, mj = self._pad_rows(x_all, y_std, rows, d)
            with telemetry.span("suggest.gphp_fit", n=n):
                samples = self._fit_gphps(xj, yj, mj)  # consumes one RNG key
            cache.samples = np.asarray(samples)
            cache.obs_since_refit = 0
            if pool is not None:
                pool.publish(cache.samples, self._chain_state)
                cache.pool_version = pool.version
            with telemetry.span("suggest.factorize", n=n):
                post = self._factorize(xj, yj, mj)
        elif not post_valid:
            # Cached draws (restored from a checkpoint/snapshot, adopted from
            # the pool, or arena-evicted factors) but no live factorization.
            # The factors the uninterrupted engine holds were built by a full
            # factorization at its last refit/adoption boundary followed by
            # rank-1 appends — so the rebuild must *replay* that exact op
            # sequence, not refactorize at n: a size-n Cholesky differs from
            # factorize(r)+appends in the last bits, which would silently
            # break the bit-equivalence contract of engine snapshots
            # (``SelectionService.restore_job``) and arena eviction. RNG-free.
            # The subset backend keeps the invariant: its inducing set is a
            # deterministic function of the store prefix at the boundary, so
            # re-selecting over [0, r) reproduces the evicted/snapshotted
            # factor layout bit-exactly before the appends replay.
            r = min(n, max(2, acct - cache.obs_since_refit))
            cache.obs_since_refit += new_obs
            rows = self._boundary_rows(x_all[:r], r)
            xj, yj, mj = self._pad_rows(x_all, y_std, rows, d)
            with telemetry.span("suggest.factor_rebuild", n=n, boundary=r):
                post = self._factorize(xj, yj, mj)
                post = self._append_rows(post, store, r, n, live0=len(rows))
        else:
            live0 = (
                acct
                if cache.inducing_sel is None
                else len(cache.inducing_sel) + (acct - cache.inducing_n0)
            )
            with telemetry.span("suggest.rank1_append", n=n, new=new_obs):
                post = self._append_rows(cache.post, store, acct, n, live0=live0)
            cache.obs_since_refit += new_obs

        cache.n = n
        cache.token = token
        return post

    def _boundary_rows(self, x_prefix: np.ndarray, r: int) -> np.ndarray:
        """Live store rows of a factorization at boundary ``r`` — all of
        ``[0, r)`` on the exact backend, the greedy max-diversity inducing
        set on the subset backend once the boundary reaches ``n_switch``.
        Records the selection on the cache (``inducing_sel``/``inducing_n0``)
        so the append path and target gathering agree with the factor."""
        cfg = self.config
        cache = self.cache
        if cfg.posterior_backend == "subset" and r >= cfg.n_switch:
            sel = select_inducing(x_prefix, cfg.max_inducing)
            cache.inducing_sel = sel
            cache.inducing_n0 = r
            return sel
        cache.inducing_sel = None
        cache.inducing_n0 = 0
        return np.arange(r, dtype=np.int64)

    @staticmethod
    def _pad_rows(x_all: np.ndarray, y_std: np.ndarray, rows: np.ndarray, d):
        """Gather + bucket-pad the live rows for fitting/factorization."""
        nlive = len(rows)
        nb = bucket_size(nlive)
        x_pad = np.zeros((nb, d))
        y_pad = np.zeros((nb,))
        x_pad[:nlive] = x_all[rows]
        y_pad[:nlive] = y_std[rows]
        mask = np.zeros(nb, dtype=bool)
        mask[:nlive] = True
        return jnp.asarray(x_pad), jnp.asarray(y_pad), jnp.asarray(mask)

    def _factorize(self, xj, yj, mj):
        """Factorize the masked rows under the cached GPHP draws, with L⁻¹.
        The acquisition refinement (``gp.predict``) and the Pallas
        anchor-scoring kernel both read it; building it here, whatever the
        scoring backend, lets every decision (and fantasy append) reuse the
        cached inverse and keeps the two backends' refinements in the same
        arithmetic."""
        params_batch = gpparams.GPHyperParams.unpack(
            jnp.asarray(self.cache.samples), self.space.encoded_dim
        )
        return gplib.fit_posterior_batch(
            xj, yj, params_batch, mj, backend=self.config.fit_backend,
            with_inverse=True,
        )

    def _factorize_with(self, samples, xj, yj, mj):
        """Factorize under an explicit draw set (per-head factors; the
        per-head scorer is jnp-only, so no L⁻¹ cache is built)."""
        params_batch = gpparams.GPHyperParams.unpack(
            jnp.asarray(samples), self.space.encoded_dim
        )
        return gplib.fit_posterior_batch(
            xj, yj, params_batch, mj, backend=self.config.fit_backend,
            with_inverse=False,
        )

    def _head_posteriors_for(self, store: ObservationStore, post, y_heads, n):
        """Per-head posteriors for ``BOConfig.per_head_gphp`` — one GPHP
        chain and one factor per extra head, mirroring the objective factor's
        lifecycle exactly: re-fitted at the objective's refit/adoption
        boundaries (one RNG key per head, in head order), rank-1-appended
        between boundaries, and rebuilt RNG-free after a restore or arena
        eviction (the factor is X-only, so the replay needs no targets).
        Alphas are refreshed against the current head targets every decision.
        Returns the posts in head order (head 1 first)."""
        cache = self.cache
        m_extra = y_heads.shape[0] - 1
        xj, mj = post.x_train, post.mask
        stale = (
            cache.head_samples is None or len(cache.head_samples) != m_extra
        )
        if self._boundary_refit or stale:
            samples, posts = [], []
            for j in range(m_extra):
                yj = jnp.asarray(y_heads[j + 1])
                s = self._fit_gphps(xj, yj, mj, chain_slot=j)
                samples.append(np.asarray(s))
                posts.append(self._factorize_with(s, xj, yj, mj))
            cache.head_samples = samples
            cache.head_posts = posts
            cache.head_n = n
        elif cache.head_posts is None:
            # RNG-free rebuild: replay factorize-at-boundary + appends (same
            # invariant as the objective factor; see ``_posterior_for``)
            b = n - cache.obs_since_refit
            rows_b = (
                cache.inducing_sel
                if cache.inducing_sel is not None
                else np.arange(b, dtype=np.int64)
            )
            nlive = len(rows_b)
            nb = bucket_size(nlive)
            x_pad = np.zeros((nb, self.space.encoded_dim))
            for k_, i in enumerate(rows_b):
                x_pad[k_] = store.x_rows(int(i), int(i) + 1)[0]
            mask = np.zeros(nb, dtype=bool)
            mask[:nlive] = True
            posts = []
            for j in range(m_extra):
                hp = self._factorize_with(
                    cache.head_samples[j],
                    jnp.asarray(x_pad),
                    jnp.zeros(nb),
                    jnp.asarray(mask),
                )
                posts.append(self._append_rows(hp, store, b, n, live0=nlive))
            cache.head_posts = posts
            cache.head_n = n
        elif cache.head_n < n:
            posts = []
            for hp in cache.head_posts:
                live0 = int(np.asarray(hp.mask).sum())
                posts.append(
                    self._append_rows(hp, store, cache.head_n, n, live0=live0)
                )
            cache.head_posts = posts
            cache.head_n = n
        out = []
        for j, hp in enumerate(cache.head_posts):
            yj = np.zeros(hp.x_train.shape[0])
            m_copy = min(yj.shape[0], y_heads.shape[1])
            yj[:m_copy] = y_heads[j + 1, :m_copy]
            out.append(refresh_alpha(hp, jnp.asarray(yj)))
        cache.head_posts = out
        return tuple(out)

    def _append_rows(
        self,
        post,
        store: ObservationStore,
        start: int,
        stop: int,
        live0: Optional[int] = None,
    ):
        """Rank-1-append store rows [start, stop), growing the shape bucket
        per row. Growth points depend only on the live-row count — never on
        how many rows one decision happened to fold — so the factor state is
        a path-independent function of (draws, rows, refit boundary);
        rebuilds (eviction, snapshot restore) replay it bit-exactly.

        ``live0`` is the number of live rows the factor holds before the
        first append. It equals ``start`` on the exact backend (store row ==
        factor row) but is the inducing count plus post-boundary appends on
        the subset backend, where the factor is smaller than the store."""
        backend = self.config.fit_backend
        if live0 is None:
            live0 = start
        for i in range(start, stop):
            live = live0 + (i - start)
            nb_i = bucket_size(live + 1)
            if post.x_train.shape[0] < nb_i:
                post = grow_posterior(post, nb_i)
            post = posterior_append(
                post, jnp.asarray(store.x_rows(i, i + 1)[0]), backend=backend
            )
        return post

    def _fantasy_append(self, work, y_work: List[float], x_vec: np.ndarray):
        """Fold a fantasized observation (pending candidate or interim batch
        pick) into the scratch posterior via the rank-1 append."""
        cfg = self.config
        if cfg.pending_strategy == "kb":
            mu, _ = gplib.predict(
                work, jnp.asarray(x_vec)[None, :], backend=cfg.fit_backend
            )
            val = float(jnp.mean(mu))  # kriging believer: integrated post. mean
        else:
            val = cfg.liar_value  # constant liar in standardized space
        live = len(y_work)
        if live >= work.x_train.shape[0]:
            work = grow_posterior(work, bucket_size(live + 1))
        work = posterior_append(work, jnp.asarray(x_vec), backend=cfg.fit_backend)
        y_work = y_work + [val]
        y_pad = np.zeros(work.x_train.shape[0])
        y_pad[: len(y_work)] = y_work
        return refresh_alpha(work, jnp.asarray(y_pad)), y_work

    def _fantasy_append_block(
        self, work, y_work: List[float], x_block: np.ndarray
    ):
        """Rank-k blocked fantasy fold (``BOConfig.fantasy_block``): one
        blocked triangular solve per GPHP sample folds the whole pending set
        (constant-liar values only — they don't depend on earlier
        fantasies). Numerically within rounding of the sequential rank-1
        path; the stream-identity test pins that suggestions agree."""
        cfg = self.config
        k = len(x_block)
        live = len(y_work)
        need = bucket_size(live + k)
        if work.x_train.shape[0] < need:
            work = grow_posterior(work, need)
        work = posterior_append_block(
            work, jnp.asarray(x_block), backend=cfg.fit_backend
        )
        y_work = y_work + [cfg.liar_value] * k
        y_pad = np.zeros(work.x_train.shape[0])
        y_pad[: len(y_work)] = y_work
        return refresh_alpha(work, jnp.asarray(y_pad)), y_work

    # ---------------------------------------------------------------- gphps
    def _fit_gphps(
        self, xj, yj, mj, chain_slot: Optional[int] = None
    ) -> jax.Array:
        """Sample/optimize packed GPHPs; returns (S, 3d+2) packed draws.
        ``chain_slot=None`` is the objective chain; slot ``j`` is the
        warm-start state of extra head ``j+1`` (``per_head_gphp``)."""
        cfg = self.config
        d = self.space.encoded_dim
        bounds = self._bounds
        init = gpparams.default_params(d).pack()
        init = jnp.clip(init, bounds.lower + 1e-4, bounds.upper - 1e-4)
        prev_state = (
            self._chain_state
            if chain_slot is None
            else self._head_chain_states.get(chain_slot)
        )
        if prev_state is not None:
            prev = jnp.asarray(prev_state)
            init = jnp.clip(prev, bounds.lower + 1e-4, bounds.upper - 1e-4)

        if cfg.gphp_method == "map":
            fit, fit_cfg = map_gphps, cfg.eb_config
        else:
            fit, fit_cfg = mcmc_gphps, cfg.slice_config
        args = (xj, yj, mj, bounds, init, self._next_key())
        if cfg.fit_on_host:
            host = jax.devices("cpu")[0]
            with jax.default_device(host):
                out = fit(*jax.device_put(args, host), fit_cfg, cfg.fit_backend)
            out = jnp.asarray(np.asarray(out))
        else:
            out = fit(*args, fit_cfg, cfg.fit_backend)
        if cfg.gphp_method == "map":
            self._set_chain_state(chain_slot, np.asarray(out))
            return out[None, :]
        self._set_chain_state(chain_slot, np.asarray(out[-1]))
        return out

    def _set_chain_state(
        self, chain_slot: Optional[int], state: np.ndarray
    ) -> None:
        if chain_slot is None:
            self._chain_state = state
        else:
            self._head_chain_states[chain_slot] = state

    # ---------------------------------------------------------- cold starts
    def _seen_matrix(
        self,
        x_all: np.ndarray,
        pend_np: np.ndarray,
        picks: Sequence[np.ndarray],
    ) -> np.ndarray:
        parts = [x_all]
        if len(pend_np):
            parts.append(pend_np)
        if picks:
            parts.append(np.stack(picks, axis=0))
        return np.concatenate(parts, axis=0) if parts else x_all

    def _quasi_random(
        self, seen: np.ndarray
    ) -> Tuple[Dict[str, Any], np.ndarray]:
        """Sobol cold-start / dedupe fallback (§2.1), avoiding ``seen`` rows."""
        for _ in range(32):
            vec = self.space.round_trip(self._sobol_init.next(1)[0])
            if len(seen) == 0 or np.min(
                np.max(np.abs(seen - vec[None, :]), axis=1)
            ) > self.config.dedupe_tol:
                return self.space.decode(vec), vec
        vec = self.space.round_trip(self._rng.random(self.space.encoded_dim))
        return self.space.decode(vec), vec

    # ------------------------------------------------------------ state i/o
    def state_dict(self) -> Dict[str, Any]:
        """JSON-safe image of everything drawn since construction: slice-chain
        state, numpy/JAX RNG streams, Sobol position, cached GPHP draws and
        refit-cadence counters. Pair with the construction ``seed`` to rebuild
        this engine exactly (factors rehydrate RNG-free)."""
        state = {
            "chain_state": None
            if self._chain_state is None
            else self._chain_state.tolist(),
            "sobol_count": self._sobol_init._count,
            # numpy bit-generator state: the ``_quasi_random`` dedupe fallback
            # draws from ``_rng``, so omitting it would make a restored job
            # diverge from an uninterrupted one the first time the fallback
            # fires (the checkpoint contract is bit-identical GP state).
            "rng_state": self._rng.bit_generator.state,
            "key": np.asarray(self._key).tolist(),
            # incremental-engine cadence: cached GPHP draws persist so a
            # restored job resumes the exact refit schedule (and RNG stream).
            "cached_samples": None
            if self.cache.samples is None
            else np.asarray(self.cache.samples).tolist(),
            "cached_n": self.cache.n,
            "obs_since_refit": self.cache.obs_since_refit,
            # per-head GPHP chains (per_head_gphp; None/absent when off)
            "head_chain_states": {
                str(k): v.tolist()
                for k, v in self._head_chain_states.items()
            }
            or None,
            "cached_head_samples": None
            if self.cache.head_samples is None
            else [np.asarray(s).tolist() for s in self.cache.head_samples],
            "cached_head_n": self.cache.head_n,
        }
        # multi-fidelity rung tables ride the suggester state so both the
        # Tuner checkpoint and the remote EngineState/EngineRestore RPCs carry
        # them without a new channel; key absent when MF is off keeps old
        # checkpoints byte-identical.
        if self.multi_fidelity_state is not None:
            state["multi_fidelity"] = self.multi_fidelity_state.snapshot()
        # budget ledger spend rides the same channel (checkpoints, engine
        # snapshots, EngineState RPC); key absent when budgets are off keeps
        # cost-off state byte-identical to the pre-budget schema.
        if self.budget_ledger is not None:
            state["budget"] = self.budget_ledger.snapshot()
        return state

    def load_state_dict(self, state: Mapping[str, Any]) -> None:
        """Install ``state_dict()`` output into a suggester constructed with
        the same (space, config, seed); the next decision continues the
        original stream bit-exactly."""
        cs = state.get("chain_state")
        self._chain_state = None if cs is None else np.asarray(cs)
        self._sobol_init.reset()
        if state.get("sobol_count", 0):
            self._sobol_init.next(int(state["sobol_count"]))
        if state.get("rng_state") is not None:
            self._rng.bit_generator.state = state["rng_state"]
        self._key = jnp.asarray(np.asarray(state["key"], dtype=np.uint32))
        samples = state.get("cached_samples")
        self.cache.samples = None if samples is None else np.asarray(samples)
        self.cache.n = int(state.get("cached_n", 0))
        self.cache.obs_since_refit = int(state.get("obs_since_refit", 0))
        self.cache.post = None  # refactorized lazily from cached samples
        self.cache.token = None
        self.cache.inducing_sel = None  # re-selected in the RNG-free rebuild
        self.cache.inducing_n0 = 0
        hcs = state.get("head_chain_states") or {}
        self._head_chain_states = {
            int(k): np.asarray(v) for k, v in hcs.items()
        }
        hs = state.get("cached_head_samples")
        self.cache.head_samples = (
            None if hs is None else [np.asarray(s) for s in hs]
        )
        self.cache.head_n = int(state.get("cached_head_n", 0))
        self.cache.head_posts = None  # rebuilt lazily, like the objective's
        self.cache.head_alphas = None
        mf = state.get("multi_fidelity")
        if mf is not None and self.multi_fidelity_state is not None:
            self.multi_fidelity_state.load_snapshot(mf)
        bud = state.get("budget")
        if bud is not None and self.budget_ledger is not None:
            self.budget_ledger.load_snapshot(bud)
        self._wrapper_store = None
        self._wrapper_fps = []


class RandomSuggester:
    """Uniform random search (paper §2.1) — respects log scaling (§5.1)."""

    def __init__(self, space: SearchSpace, seed: int = 0):
        self.space = space
        self._rng = np.random.default_rng(seed)  # invariant: fresh-rng -- constructor-seeded; bit-generator state round-trips through state_dict/load_state_dict

    def suggest(
        self,
        history: Sequence[Observation] = (),
        pending: Sequence[Mapping[str, Any]] = (),
    ) -> Dict[str, Any]:
        return self.space.sample(self._rng, 1)[0]

    def suggest_batch(self, k: int) -> List[Dict[str, Any]]:
        return self.space.sample(self._rng, k)

    def state_dict(self) -> Dict[str, Any]:
        return {"bitgen": self._rng.bit_generator.state}

    def load_state_dict(self, state: Mapping[str, Any]) -> None:
        self._rng.bit_generator.state = state["bitgen"]


class SobolSuggester:
    """Quasi-random Sobol search (paper §2.1: better space coverage)."""

    def __init__(self, space: SearchSpace, seed: int = 0):
        self.space = space
        self._seq = SobolSequence(space.encoded_dim, shift_rng=np.random.default_rng(seed))  # invariant: fresh-rng -- shift scramble is a pure function of the seed; the sequence position (_count) is the only replay state
        self._count = 0

    def suggest(self, history=(), pending=()) -> Dict[str, Any]:
        return self.suggest_batch(1)[0]

    def suggest_batch(self, k: int) -> List[Dict[str, Any]]:
        self._count += k
        return [
            self.space.decode(self.space.round_trip(v)) for v in self._seq.next(k)
        ]

    def state_dict(self) -> Dict[str, Any]:
        return {"count": self._count}

    def load_state_dict(self, state) -> None:
        self._seq.reset()
        self._count = int(state.get("count", 0))
        if self._count:
            self._seq.next(self._count)
