"""Step 2 of FedDCL: each user's PRIVATE dimensionality-reduction map f_j^(i).

A mapping is a linear row-wise map f(X) = (X - mu) W with W ∈ R^{m × m̃},
never shared under the protocol (privacy Layer 1). Kinds:

  pca_rot  — top-m̃ local PCA basis composed with a RANDOM ORTHOGONAL
             rotation (the paper's experimental setting): W = V_k Q.
             Range(W) = local principal subspace; the rotation makes W
             user-specific even for identical data.
  pca      — plain local PCA (used by the Theorem-1 property test: all
             users on identical data then share Range(W)).
  randproj — Gaussian random projection (Johnson-Lindenstrauss), data-free.
  fixed    — externally supplied W (test hook for same-range constructions).

Nonlinear maps are supported by composing `apply` with any row-wise
nonlinearity upstream; the paper's experiments (and ours) use linear maps.

`fit_maps` runs step 2 for a list of users at once, concurrently, as their
institutions would each on its own machine.
"""
from __future__ import annotations

import functools
import os
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np
from threadpoolctl import ThreadpoolController

from repro import obs


@dataclass
class LinearMap:
    mu: np.ndarray        # (m,)
    W: np.ndarray         # (m, m_tilde)

    def __call__(self, X: np.ndarray) -> np.ndarray:
        return (X - self.mu[None, :]) @ self.W

    @property
    def out_dim(self) -> int:
        return self.W.shape[1]


def _random_orthogonal(rng, k: int) -> np.ndarray:
    Q, R = np.linalg.qr(rng.standard_normal((k, k)))
    return Q * np.sign(np.diag(R))[None, :]


def fit_mapping(kind: str, X: np.ndarray, m_tilde: int,
                seed: int = 0, center: bool = True,
                W: Optional[np.ndarray] = None) -> LinearMap:
    rng = np.random.default_rng(seed)
    m = X.shape[1]
    mu = X.mean(axis=0) if center else np.zeros(m)
    if kind == "fixed":
        assert W is not None
        return LinearMap(mu=mu, W=np.asarray(W, np.float64))
    if kind == "randproj":
        Wr = rng.standard_normal((m, m_tilde)) / np.sqrt(m_tilde)
        return LinearMap(mu=mu, W=Wr)
    # PCA variants
    Xc = X - mu[None, :]
    _, _, Vt = np.linalg.svd(Xc, full_matrices=False)
    V = Vt[:m_tilde].T                                  # (m, m̃)
    if kind == "pca":
        return LinearMap(mu=mu, W=V)
    if kind == "pca_rot":
        Q = _random_orthogonal(rng, m_tilde)
        return LinearMap(mu=mu, W=V @ Q)
    raise ValueError(f"unknown mapping kind {kind!r}")


def fit_maps(kind: str, Xs: Sequence[np.ndarray], anchor: np.ndarray,
             m_tilde: int, seeds: Sequence[int],
             W: Optional[np.ndarray] = None
             ) -> List[Tuple[LinearMap, np.ndarray, np.ndarray]]:
    """Step 2 for a list of users: each user's private map f (fitted from
    its own data and seed), X̃ = f(X) and Ã = f(anchor), in the order of
    `Xs`.

    Each result depends only on its own user, so the users are fitted
    concurrently: min(users, cores) of them at a time in a thread pool
    kept for the process, with OpenBLAS held to cores // workers threads
    meanwhile (its full thread pool costs more than the arithmetic of one
    user's small SVD and products, and several callers contend for it).
    One user, or one core, is fitted inline at the default BLAS threads.
    """
    Xs = [np.asarray(X, np.float64) for X in Xs]

    def fit(X, seed):
        f = fit_mapping(kind, X, m_tilde, seed=seed, W=W)
        return f, f(X), f(anchor)

    cores = len(os.sched_getaffinity(0))
    workers = min(len(Xs), cores)
    if workers <= 1:
        return [fit(X, s) for X, s in zip(Xs, seeds)]
    pool, blas = _step2_pool()
    # one fan-out at a time, so each restores the BLAS limit it found
    with _step2_lock, blas.limit(limits=max(1, cores // workers),
                                 user_api="blas"):
        futures = [pool.submit(fit, X, s) for X, s in zip(Xs, seeds)]
        wait(futures)
    out = [fu.result() for fu in futures]
    obs.count("feddcl.protocol.step2_concurrent_maps", len(out))
    return out


_step2_lock = threading.Lock()


@functools.cache
def _step2_pool() -> Tuple[ThreadPoolExecutor, ThreadpoolController]:
    """The thread pool and BLAS controller `fit_maps` fans out with, made
    on first use and kept for the process."""
    return (ThreadPoolExecutor(len(os.sched_getaffinity(0)),
                               thread_name_prefix="feddcl-step2"),
            ThreadpoolController())
