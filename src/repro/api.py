"""One-call public API: ``FedDCL().fit(Xs, Ys)`` — protocol steps 1–3 plus
the compiled FL phase, through the compiled-plan cache.

The paper's pitch is that institutions pay for communication once and
amortize everything else; this facade makes the COMPUTE side match. The
first ``fit()`` of a given shape bucket pays the scan-engine trace+compile
(~1 s on CPU); every later ``fit()`` whose padded shapes land in the same
bucket reuses the executable and costs milliseconds (the plan cache,
core/federated.py, DESIGN.md §6). Across processes, the persistent XLA
compilation cache (`enable_persistent_compilation_cache`) turns even the
first call of a fresh process into a disk hit.

    from repro.api import FedDCL
    model = FedDCL(m_tilde=8, rounds=20, local_epochs=4, task="regression")
    setup, result = model.fit(Xs, Ys)      # Xs[i][j]: raw data of user (i,j)
    yhat = model.predict(Xnew)             # through user (0,0)'s transform
    result.cache_stats                     # {'hit': ..., 'misses': ...}

Everything is keyword-configured with the paper's §4.1 defaults; the
returned ``setup`` is the full FedDCLSetup (mappings, G's, comm log) and
``result`` the FLResult of the federated phase.
"""
from __future__ import annotations

import pathlib
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental.compilation_cache import compilation_cache

from repro.core import protocol
from repro.core.federated import (FLResult, PlanCache, default_plan_cache,
                                  run_federated)
from repro.core.protocol import FedDCLSetup
from repro.models import mlp
from repro.optim import adamw

# fixed in-checkout default: the cache directory is part of what makes a
# later run hit, so it must never be a temporary or per-process path
DEFAULT_COMPILATION_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")

_COMPILE_CACHE_DIR: Optional[str] = None


def enable_persistent_compilation_cache() -> str:
    """Turn on XLA's persistent compilation cache so compiled executables
    survive process boundaries; returns the active directory. Idempotent.

    The directory is JAX's own setting (``JAX_COMPILATION_CACHE_DIR``) when
    one is configured, and then nothing else is set; otherwise it is
    `DEFAULT_COMPILATION_CACHE_DIR`, a fixed path inside the checkout.
    Thresholds are dropped to zero because the FL-phase programs are small,
    fast-compiling HLO by XLA's heuristics yet dominate our cold time.
    """
    global _COMPILE_CACHE_DIR
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = DEFAULT_COMPILATION_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if _COMPILE_CACHE_DIR == cache_dir:
        return cache_dir
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # jax latches cache-off at the first compile of the process; reset so
    # enabling mid-process (any compile may already have happened) works
    compilation_cache.reset_cache()
    _COMPILE_CACHE_DIR = cache_dir
    return cache_dir


class FedDCL:
    """sklearn-style facade over the full FedDCL pipeline.

    ``fit(Xs, Ys)`` runs Algorithm 1 end to end: anchor + private mappings
    (steps 1–2), the two-level collaboration solve (step 3, `svd_backend`),
    then the federated phase (step 4) on the collaboration representations
    through ``run_federated`` — by default on the compiled scan engine via
    the shared plan cache, with stable loss/optimizer cache identities so
    repeated fits and sweeps reuse executables.

    Model head: an MLP on the m̂-dimensional collaboration representations
    (`hidden`, `task`; `out_dim` inferred from Ys when None).
    """

    def __init__(self, *, m_tilde: int, m_hat: Optional[int] = None,
                 hidden: Sequence[int] = (32,), task: str = "regression",
                 out_dim: Optional[int] = None,
                 rounds: int = 20, local_epochs: int = 4,
                 batch_size: int = 32, lr: float = 1e-3,
                 aggregator: str = "fedavg", fedprox_mu: float = 0.0,
                 anchor_r: int = 2000, anchor_kind: str = "uniform",
                 mapping_kind: str = "pca_rot", svd_backend: str = "host",
                 engine: str = "scan", seed: int = 0,
                 reset_opt_per_round: bool = True,
                 cache: Any = True,
                 eval_fn: Optional[Callable[[Any], Dict[str, float]]] = None,
                 dropout_rate: float = 0.0,
                 silo_scale: Optional[Sequence[float]] = None,
                 trim_frac: float = 0.2, krum_f: int = 1,
                 onboard: bool = True):
        self.m_tilde = m_tilde
        self.m_hat = m_hat or m_tilde
        self.hidden = tuple(hidden)
        self.task = task
        self.out_dim = out_dim
        self.rounds = rounds
        self.local_epochs = local_epochs
        self.batch_size = batch_size
        self.lr = lr
        self.aggregator = aggregator
        self.fedprox_mu = fedprox_mu
        self.anchor_r = anchor_r
        self.anchor_kind = anchor_kind
        self.mapping_kind = mapping_kind
        self.svd_backend = svd_backend
        self.engine = engine
        self.seed = seed
        self.reset_opt_per_round = reset_opt_per_round
        self.cache = cache
        self.eval_fn = eval_fn
        # hostile-world federation knobs (DESIGN.md §8): aggregator may be
        # any of federated.AGGREGATORS incl. the robust ones; dropout_rate
        # simulates silo unavailability; silo_scale is the attack-injection
        # vector (experiments/robust_ablation.py exercises all of these)
        self.dropout_rate = dropout_rate
        self.silo_scale = silo_scale
        self.trim_frac = trim_frac
        self.krum_f = krum_f
        # onboard=True keeps the incremental-update state (cached Grams and
        # QR factors, DESIGN.md §10) so partial_fit()/serve().onboard_* can
        # admit tenants without a full protocol recompute
        self.onboard = onboard
        # one optimizer per estimator: its identity is stable across fit()s
        self._opt = adamw(lr)
        self.setup_: Optional[FedDCLSetup] = None
        self.result_: Optional[FLResult] = None

    # -- pipeline ----------------------------------------------------------

    def _infer_out_dim(self, Ys) -> int:
        if self.out_dim is not None:
            return self.out_dim
        y0 = np.asarray(Ys[0][0])
        if self.task == "classification":
            return int(max(int(np.asarray(y).max()) for g in Ys for y in g)) + 1
        return 1 if y0.ndim == 1 else int(y0.shape[-1])

    def fit(self, Xs: Sequence[Sequence[np.ndarray]],
            Ys: Sequence[Sequence[np.ndarray]],
            init_params: Any = None) -> Tuple[FedDCLSetup, FLResult]:
        """Run the whole protocol; returns (setup, fl_result) and stores
        them on the estimator (`setup_`, `result_`, `params_`)."""
        enable_persistent_compilation_cache()
        setup = protocol.run_protocol(
            Xs, Ys, m_tilde=self.m_tilde, m_hat=self.m_hat,
            anchor_r=self.anchor_r, anchor_kind=self.anchor_kind,
            mapping_kind=self.mapping_kind, seed=self.seed,
            svd_backend=self.svd_backend, onboard=self.onboard)
        out_dim = self._infer_out_dim(Ys)
        params = init_params if init_params is not None else mlp.init_mlp_params(
            jax.random.PRNGKey(self.seed), self.m_hat, self.hidden, out_dim)
        loss = partial(mlp.mlp_per_example_loss, task=self.task)
        result = run_federated(
            loss, params, setup.fed_silos(), opt=self._opt,
            rounds=self.rounds, local_epochs=self.local_epochs,
            batch_size=self.batch_size, aggregator=self.aggregator,
            fedprox_mu=self.fedprox_mu, seed=self.seed, eval_fn=self.eval_fn,
            engine=self.engine, cache=self.cache if self.engine == "scan" else None,
            loss_id=("mlp_per_example_loss", self.task),
            opt_id=("adamw", self.lr),
            dropout_rate=self.dropout_rate, silo_scale=self.silo_scale,
            trim_frac=self.trim_frac, krum_f=self.krum_f)
        self.setup_, self.result_ = setup, result
        self.params_ = result.params
        return setup, result

    # -- incremental onboarding (DESIGN.md §10) ----------------------------

    def partial_fit(self, X_new: Any, Y_new: Any, *,
                    group: Optional[int] = None,
                    refit_rounds: Optional[int] = None) -> Tuple[int, int]:
        """Onboard new data onto a FITTED estimator without recomputing the
        protocol: with ``group=i``, (X_new, Y_new) is ONE new user joining
        group i; with ``group=None``, they are lists of per-user arrays
        forming a whole new silo. The collaboration solve updates
        incrementally (blocked Gram + cached factors; equal to a from-scratch
        ``run_protocol`` on the same anchor, tested to 1e-5).

        ``refit_rounds`` optionally continues federated training for that
        many rounds on the refreshed representations, warm-starting from the
        current params (the central SVD moved, so every silo's X̂ changed
        slightly). Returns the (group, user) index of the newcomer.
        """
        if self.setup_ is None:
            raise RuntimeError("call fit() before partial_fit()")
        if group is None:
            i = self.setup_.onboard_silo(list(X_new), list(Y_new))
            j = 0
        else:
            i = int(group)
            j = self.setup_.onboard_user(i, X_new, Y_new)
        if refit_rounds:
            loss = partial(mlp.mlp_per_example_loss, task=self.task)
            result = run_federated(
                loss, self.params_, self.setup_.fed_silos(), opt=self._opt,
                rounds=int(refit_rounds), local_epochs=self.local_epochs,
                batch_size=self.batch_size, aggregator=self.aggregator,
                fedprox_mu=self.fedprox_mu, seed=self.seed + 1,
                eval_fn=self.eval_fn, engine=self.engine,
                cache=self.cache if self.engine == "scan" else None,
                loss_id=("mlp_per_example_loss", self.task),
                opt_id=("adamw", self.lr),
                dropout_rate=self.dropout_rate, silo_scale=self.silo_scale,
                trim_frac=self.trim_frac, krum_f=self.krum_f)
            self.result_ = result
            self.params_ = result.params
        return i, j

    def serve(self, **kw) -> Any:
        """A live ``ServeCollab`` server over the fitted model: queued,
        bucketed, continuously-admitted inference for every tenant, with
        ``onboard_user``/``onboard_silo`` for admitting tenants in place."""
        from repro.serve_collab import ServeCollab
        return ServeCollab.from_model(self, **kw)

    # -- inference ---------------------------------------------------------

    def transform(self, X: np.ndarray, i: int = 0, j: int = 0) -> np.ndarray:
        """x → f_j^(i)(x) G_j^(i): user (i,j)'s input map."""
        if self.setup_ is None:
            raise RuntimeError("call fit() first")
        return np.asarray(self.setup_.user_transform(i, j)(X))

    def predict(self, X: np.ndarray, i: int = 0, j: int = 0) -> np.ndarray:
        """t_j^(i)(X) = h(f(X) G): regression values or class labels."""
        if self.result_ is None:
            raise RuntimeError("call fit() first")
        out = np.asarray(mlp.mlp_forward(self.params_,
                                         np.asarray(self.transform(X, i, j),
                                                    np.float32)))
        return out.argmax(-1) if self.task == "classification" else out

    def score(self, X: np.ndarray, Y: np.ndarray, i: int = 0, j: int = 0) -> float:
        """RMSE (regression) / accuracy (classification) through (i,j)."""
        import jax.numpy as jnp
        Xt = jnp.asarray(self.transform(X, i, j), jnp.float32)
        return mlp.mlp_metric(self.params_, Xt, jnp.asarray(Y), self.task)
