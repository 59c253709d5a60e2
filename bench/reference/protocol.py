"""Plain reference of FedDCL steps 1-3 (arXiv:2409.18356, Algorithm 1 and
eqs. 1-3) in NumPy float64, written from the paper and the deployment's
stated settings, importing nothing of the program.

The stated settings are those every FedDCL deployment of this benchmark
runs: a uniform anchor inside the pooled per-feature ranges (step 1); each
user's private map the top-m̃ local PCA basis times a random orthogonal
rotation (step 2); intra-group bases B̃ = U C1 with the paper's
obfuscation C1 = Σ V_jᵀ E (random user block j, random orthogonal E, with a
scaled random orthogonal fallback when that product is ill-conditioned),
the central target Z = P C2 likewise, and G = argmin ‖Ã G − Z‖ (step 3).
The random streams are the deployment's: anchor from the seed, user (i,j)'s
rotation from seed·1009 + 101·i + j, group i's obfuscation from
seed·31 + i, the central one from seed·57. Singular vectors are signed so
that each right vector's largest-magnitude entry is positive.

``lowp=True`` computes every product and decomposition of steps 1-3 on
operands rounded to bfloat16, accumulated in float32: the control that the
comparison must reject.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import ml_dtypes
import numpy as np


def _round(x: np.ndarray, lowp: bool) -> np.ndarray:
    if not lowp:
        return np.asarray(x, np.float64)
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def mm(a: np.ndarray, b: np.ndarray, lowp: bool = False) -> np.ndarray:
    """a @ b in float64, or on bfloat16-rounded operands for the control."""
    return _round(a, lowp) @ _round(b, lowp)


def _random_orthogonal(rng, k: int) -> np.ndarray:
    Q, R = np.linalg.qr(rng.standard_normal((k, k)))
    return Q * np.sign(np.diag(R))[None, :]


def _signed(U, s, V):
    idx = np.argmax(np.abs(V), axis=0)
    flip = np.sign(V[idx, np.arange(V.shape[1])])
    flip = np.where(flip == 0, 1.0, flip)
    return U * flip[None, :], s, V * flip[None, :]


def _topk(A: np.ndarray, k: int, lowp: bool):
    """Rank-k singular triple: float64 SVD, or for the control the
    eigenpairs of a bfloat16-operand Gram (what a low-precision
    implementation would compute)."""
    k = int(min(k, *A.shape))
    if not lowp:
        U, s, Vt = np.linalg.svd(A, full_matrices=False)
        return _signed(U[:, :k], s[:k], Vt[:k].T)
    ev, vecs = np.linalg.eigh(mm(A.T, A, True).astype(np.float32))
    s = np.sqrt(np.maximum(ev[::-1][:k], 0.0))
    V = vecs[:, ::-1][:, :k]
    U = mm(A, V, True) / np.maximum(s, 1e-12)[None, :]
    return _signed(U, s, V)


def _obfuscation(rng, s, V, block_cols, k: int, lowp: bool) -> np.ndarray:
    j = int(rng.integers(0, len(block_cols)))
    lo = int(np.sum(block_cols[:j]))
    Vb = V[lo:lo + int(block_cols[j]), :]
    if Vb.shape[0] == k:
        C = mm(s[:, None] * Vb.T, _random_orthogonal(rng, k), lowp)
        if np.linalg.cond(C) < 1e8:
            return C
    return _random_orthogonal(rng, k) * s[:, None]


@dataclass
class UserMap:
    mu: np.ndarray
    W: np.ndarray


@dataclass
class Collaboration:
    """What steps 1-3 give: the anchor, user maps, intermediate anchors,
    group Grams and bases, the central target, every G and X̂."""
    anchor: np.ndarray
    maps: List[List[UserMap]]
    inter_A: List[List[np.ndarray]]
    grams: List[np.ndarray]
    bases: List[np.ndarray]
    Z: np.ndarray
    Gs: List[List[np.ndarray]]
    collab_X: List[np.ndarray]
    tables: List[np.ndarray] = field(default_factory=list)


def uniform_anchor(Xs, seed: int, r: int) -> np.ndarray:
    allX = np.concatenate([np.concatenate(list(g), axis=0) for g in Xs])
    lo, hi = allX.min(0), allX.max(0)
    u = np.random.default_rng(seed).uniform(size=(r, allX.shape[1]))
    return lo[None, :] + u * (hi - lo)[None, :]


def user_map(X: np.ndarray, m_tilde: int, seed: int) -> UserMap:
    rng = np.random.default_rng(seed)
    X = np.asarray(X, np.float64)
    mu = X.mean(axis=0)
    _, _, Vt = np.linalg.svd(X - mu[None, :], full_matrices=False)
    return UserMap(mu=mu, W=Vt[:m_tilde].T @ _random_orthogonal(rng, m_tilde))


def collaborate(Xs, *, m_tilde: int, m_hat: int, anchor_r: int, seed: int,
                anchor: Optional[np.ndarray] = None,
                lowp: bool = False) -> Collaboration:
    """Steps 1-3 over the roster Xs[i][j]."""
    if anchor is None:
        anchor = uniform_anchor(Xs, seed, anchor_r)
    maps, inter_A, inter_X = [], [], []
    for i, row in enumerate(Xs):
        maps.append([user_map(X, m_tilde, seed * 1009 + i * 101 + j)
                     for j, X in enumerate(row)])
        inter_A.append([mm(anchor - f.mu[None, :], f.W, lowp)
                        for f in maps[i]])
        inter_X.append([mm(np.asarray(X, np.float64) - f.mu[None, :], f.W,
                            lowp) for X, f in zip(row, maps[i])])
    grams, bases = [], []
    for i, row in enumerate(inter_A):
        A = np.concatenate(row, axis=1)
        grams.append(mm(A.T, A, lowp))
        U, s, V = _topk(A, m_hat, lowp)
        C1 = _obfuscation(np.random.default_rng(seed * 31 + i), s, V,
                          [a.shape[1] for a in row], U.shape[1], lowp)
        bases.append(mm(U, C1, lowp))
    B = np.concatenate(bases, axis=1)
    P, D, Q = _topk(B, m_hat, lowp)
    C2 = _obfuscation(np.random.default_rng(seed * 57), D, Q,
                      [b.shape[1] for b in bases], P.shape[1], lowp)
    Z = mm(P, C2, lowp)
    Gs, collab_X, tables = [], [], []
    for i, row in enumerate(inter_A):
        Gs.append([np.linalg.lstsq(_round(a, lowp), _round(Z, lowp),
                                   rcond=None)[0] for a in row])
        collab_X.append(np.concatenate(
            [mm(x, g, lowp) for x, g in zip(inter_X[i], Gs[i])], axis=0))
        tables.append(np.stack([mm(f.W, g, lowp)
                                for f, g in zip(maps[i], Gs[i])]))
    return Collaboration(anchor=anchor, maps=maps, inter_A=inter_A,
                         grams=grams, bases=bases, Z=Z, Gs=Gs,
                         collab_X=collab_X, tables=tables)
