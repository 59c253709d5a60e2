"""Step 3 of FedDCL: collaboration-representation construction (eqs. 1–3).

Two-level SVD protocol:
  intra-group (eq. 1):  Ã^(i) = [Ã_1^(i) … Ã_{c_i}^(i)] ≈ U^(i) Σ^(i) V^(i)ᵀ
                        B̃^(i) = U^(i) C_1^(i)          (C_1 nonsingular)
  central    (eq. 2):   B̃ = [B̃^(1) … B̃^(d)] ≈ P D Qᵀ,  Z = P C_2
  per-user   (eq. 3):   G_j^(i) = argmin_G ‖Ã_j^(i) G − Z‖_F  (least squares)

Only B̃^(i) crosses the group boundary; only Z comes back. C_1/C_2 follow the
paper's construction C_1^(i) = Σ^(i) (V_{j'}^(i))ᵀ E_1 (random orthogonal E,
randomly selected user block j'), falling back to a random orthogonal matrix
when that product is singular/non-square.

Backends (`CollabBackend`, DESIGN.md §3):
  "host"   — NumPy float64 LAPACK, faithful to the paper's MATLAB; serial
             per-group SVDs and per-user QR least squares.
  "device" — device-resident batched engine: the groups of each width go
             through ONE batched fp32 Gram reduction + batched eigh (Pallas
             `gram` kernel on TPU), and all users of the protocol through
             ONE jitted batched QR factoring and triangular solve. Ragged
             user widths are zero-padded to the max width.
  "tpu"    — alias of "device" (legacy name).
Both hand back each group's Gram and each user's QR factors beside their
answers: the onboarding state keeps them, at no second pass.

The obfuscation matrices C_1/C_2 are tiny (m̂ × m̂) and stay on host in both
backends so the two paths share identical RNG streams; because B̃ = U C_1
with C_1 = Σ V_blockᵀ E, per-pair sign flips between eigh- and SVD-derived
factors cancel and the backends agree to fp32 accuracy (tested).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

from repro import obs


def _fetch(x) -> np.ndarray:
    """A device array read to the host (counted as
    ``feddcl.device.fetches``)."""
    obs.count("feddcl.device.fetches")
    return np.asarray(x)


# --------------------------------------------------------------------------
# padded-ragged helpers
# --------------------------------------------------------------------------

def pad_ragged(mats: Sequence[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
    """Stack (r, w_b) matrices of ragged width into a zero-padded
    (B, r, w_max) array + boolean column mask (B, w_max)."""
    r = mats[0].shape[0]
    w_max = max(m.shape[1] for m in mats)
    out = np.zeros((len(mats), r, w_max), np.float32)
    mask = np.zeros((len(mats), w_max), bool)
    for b, m in enumerate(mats):
        out[b, :, : m.shape[1]] = m
        mask[b, : m.shape[1]] = True
    return out, mask


def pad_ragged2d(mats: Sequence[np.ndarray]) -> np.ndarray:
    """Stack matrices ragged in BOTH dims into a zero-padded
    (B, n_max, m_max) float32 array (no masks: callers exploit that
    zero-padding makes the products they need exact — see
    gram.ops.apply_G_batched)."""
    n_max = max(m.shape[0] for m in mats)
    m_max = max(m.shape[1] for m in mats)
    out = np.zeros((len(mats), n_max, m_max), np.float32)
    for b, m in enumerate(mats):
        out[b, : m.shape[0], : m.shape[1]] = m
    return out


def _fix_signs(U: np.ndarray, s: np.ndarray, V: np.ndarray):
    """Deterministic sign convention: make the max-|entry| of each V column
    positive, flipping the (U, V) pair jointly. SVD/eigh factorisations are
    only unique up to per-pair signs; pinning them makes every downstream
    construction — including the non-V-dependent obfuscation fallback —
    agree across backends instead of only the sign-invariant main branch."""
    idx = np.argmax(np.abs(V), axis=0)
    flip = np.sign(V[idx, np.arange(V.shape[1])])
    flip = np.where(flip == 0, 1.0, flip)
    return U * flip[None, :], s, V * flip[None, :]


# --------------------------------------------------------------------------
# backends
# --------------------------------------------------------------------------

class HostBackend:
    """NumPy float64 LAPACK — the paper-faithful serial reference."""

    name = "host"

    def topk_svd(self, A: np.ndarray, k: int):
        k = int(min(k, *A.shape))
        U, s, Vt = np.linalg.svd(np.asarray(A, np.float64), full_matrices=False)
        return _fix_signs(U[:, :k], s[:k], Vt[:k].T)

    def topk_svd_many(self, mats: Sequence[np.ndarray], k: int):
        """(svds, grams): each matrix's rank-k SVD and its `gram`."""
        return [self.topk_svd(A, k) for A in mats], [self.gram(A)
                                                     for A in mats]

    def apply_G_many(self, Xs: Sequence[np.ndarray],
                     Gs: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Per-user X̂_j = X̃_j G_j — serial float64 matmuls."""
        return [np.asarray(x, np.float64) @ g for x, g in zip(Xs, Gs)]

    # -- incremental onboarding (DESIGN.md §10) ----------------------------

    def gram(self, A: np.ndarray) -> np.ndarray:
        """AᵀA in float64 — the maintained state of a group's anchor stack."""
        A = np.asarray(A, np.float64)
        return A.T @ A

    def gram_update_blocked(self, gram: np.ndarray, A_old: np.ndarray,
                            A_new: np.ndarray) -> np.ndarray:
        """Gram([A_old A_new]) from the maintained Gram(A_old): only the
        cross and new blocks are computed — O(r·W·w) vs O(r·(W+w)²)."""
        A_old = np.asarray(A_old, np.float64)
        A_new = np.asarray(A_new, np.float64)
        cross = A_old.T @ A_new
        return np.block([[gram, cross], [cross.T, A_new.T @ A_new]])

    def topk_svd_from_gram(self, A: np.ndarray, gram: np.ndarray, k: int):
        """Rank-k singular triple recovered from the MAINTAINED Gram:
        eigh(AᵀA) gives (s², V); U = A V / s. Same sign convention as
        `topk_svd`, ~1e-10 relative agreement for separated spectra."""
        A = np.asarray(A, np.float64)
        k = int(min(k, *A.shape))
        evals, evecs = np.linalg.eigh(np.asarray(gram, np.float64))
        s = np.sqrt(np.maximum(evals[::-1][:k], 0.0))
        V = evecs[:, ::-1][:, :k]
        U = (A @ V) / np.maximum(s, 1e-12)[None, :]
        return _fix_signs(U, s, V)

    def factor_G_many(self, anchors: Sequence[np.ndarray]):
        """Per-user reduced QR of Ã_j (float64) — the Z-independent half of
        eq. (3), cached across onboarding events."""
        return [np.linalg.qr(np.asarray(a, np.float64)) for a in anchors]

    def factor_G_append(self, factors, a_new: np.ndarray):
        return list(factors) + [np.linalg.qr(np.asarray(a_new, np.float64))]

    def split_factors(self, factors, counts: Sequence[int]) -> list:
        """A flat roster's factors cut into consecutive runs of `counts`
        users, one run per group."""
        ends = np.cumsum(counts)
        return [factors[e - c:e] for c, e in zip(counts, ends)]

    def solve_G_factors(self, factors, Z: np.ndarray) -> List[np.ndarray]:
        """Eq. (3) for every user from cached factors: one triangular solve
        per user against the refreshed target, zero re-factorizations."""
        Z = np.asarray(Z, np.float64)
        return [np.linalg.solve(r, q.T @ Z) for q, r in factors]


class DeviceBackend:
    """Jitted batched path: one Gram+eigh launch per group width, one QR
    solve for all users. fp32 on-device; outputs returned as NumPy."""

    name = "device"

    def __init__(self, ridge: float = 0.0):
        # relative Tikhonov strength of the QR factoring; 0.0 keeps exact
        # lstsq agreement and requires full-column-rank anchors (the
        # protocol's generic case) — pass e.g. 1e-3 via
        # get_backend(collab.DeviceBackend(ridge=...)) for degenerate data
        self.ridge = float(ridge)

    def topk_svd(self, A: np.ndarray, k: int):
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        # clamp exactly like HostBackend.topk_svd (min(k, *A.shape))
        U, s, V = (_fetch(x)[0] for x in gram_ops.gram_eigh_topk_batched(
            jnp.asarray(A, jnp.float32)[None], int(min(k, *A.shape))))
        return _fix_signs(U, s, V)

    def topk_svd_many(self, mats: Sequence[np.ndarray], k: int):
        """(svds, grams): each matrix's rank-k SVD and its fp32 Gram, from
        one batched Gram reduction and eigh per distinct width. Zero
        columns would leave the exact top-k untouched but change the f32
        eigh's roundoff (3.4e-5 on the eigenvectors on a v5e), so a matrix
        is never padded: its factors do not depend on which other matrices
        share the call — what lets onboarding equal a from-scratch run."""
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        by_width: dict = {}
        for b, m in enumerate(mats):
            by_width.setdefault(m.shape[1], []).append(b)
        svds: list = [None] * len(mats)
        grams: list = [None] * len(mats)
        for idx in by_width.values():
            stack = jnp.asarray(np.stack([np.asarray(mats[b], np.float32)
                                          for b in idx]))
            k_b = int(min(k, *stack.shape[1:]))
            g = gram_ops.gram_batched(stack)
            U, s, V = (_fetch(x) for x in
                       gram_ops.eigh_topk_recover_batched(g, stack, k_b))
            g = _fetch(g)
            for pos, b in enumerate(idx):
                svds[b] = _fix_signs(U[pos], s[pos], V[pos])
                grams[b] = g[pos]
        return svds, grams

    def apply_G_many(self, Xs: Sequence[np.ndarray],
                     Gs: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Per-user X̂_j = X̃_j G_j for ALL users in ONE batched device
        matmul: X̃ zero-padded on both axes, G zero-padded on rows — the
        real blocks of the products are exact because padded columns of X̃
        only ever multiply zero rows of G (padded sample rows are sliced
        away)."""
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        Xp = pad_ragged2d(Xs)                             # (U, n_max, m̃_max)
        Gp = pad_ragged2d(Gs)                             # (U, m̃_max, m̂)
        out = _fetch(gram_ops.apply_G_batched(jnp.asarray(Xp),
                                              jnp.asarray(Gp)))
        return [out[u, : x.shape[0], : g.shape[1]]
                for u, (x, g) in enumerate(zip(Xs, Gs))]

    # -- incremental onboarding (DESIGN.md §10) ----------------------------

    def gram(self, A: np.ndarray) -> np.ndarray:
        """AᵀA via the device Gram reduction (fp32) — same arithmetic the
        batched from-scratch path uses, so maintained and recomputed Grams
        agree to fp32 roundoff."""
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        return _fetch(gram_ops.gram(jnp.asarray(A, jnp.float32)))

    def gram_update_blocked(self, gram: np.ndarray, A_old: np.ndarray,
                            A_new: np.ndarray) -> np.ndarray:
        """Blocked device update: one jitted launch computing only the
        cross/new blocks (gram_ops.gram_append_blocked, B=1)."""
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        out = gram_ops.gram_append_blocked(
            jnp.asarray(gram, jnp.float32)[None],
            jnp.asarray(A_old, jnp.float32)[None],
            jnp.asarray(A_new, jnp.float32)[None])
        return _fetch(out[0])

    def topk_svd_from_gram(self, A: np.ndarray, gram: np.ndarray, k: int):
        """Batched eigh+recovery from the maintained Gram (B=1) — the same
        `eigh_topk_recover_batched` tail the from-scratch device SVD runs,
        just fed the incrementally-updated Gram."""
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        k_eff = int(min(k, *A.shape))
        U, s, V = gram_ops.eigh_topk_recover_batched(
            jnp.asarray(gram, jnp.float32)[None],
            jnp.asarray(A, jnp.float32)[None], k_eff)
        return _fix_signs(_fetch(U[0]), _fetch(s[0]), _fetch(V[0]))

    def factor_G_many(self, anchors: Sequence[np.ndarray]):
        """ONE batched QR factorization of the (padded) augmented anchor
        stack — the Z-independent half of eq. (3), cached."""
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        padded, mask = pad_ragged(anchors)
        q, rr = gram_ops.solve_G_factor_batched(
            jnp.asarray(padded), jnp.asarray(mask), ridge=self.ridge)
        return {"q": q, "rr": rr, "mask": mask,
                "r": padded.shape[1],
                "widths": [a.shape[1] for a in anchors]}

    def factor_G_append(self, factors, a_new: np.ndarray):
        """Factor ONLY the joining tenant (B=1 at the stack's pad width) and
        append it to the cached factor stack. Returns None when the new
        anchor is wider than the current pad width (or taller than the
        factored row count) — the caller re-factors the whole group then."""
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        m_max = factors["mask"].shape[1]
        if a_new.shape[1] > m_max or a_new.shape[0] != factors["r"]:
            return None
        padded, mask = pad_ragged([a_new])
        if m_max > padded.shape[2]:
            pad = m_max - padded.shape[2]
            padded = np.pad(padded, ((0, 0), (0, 0), (0, pad)))
            mask = np.pad(mask, ((0, 0), (0, pad)))
        q1, rr1 = gram_ops.solve_G_factor_batched(
            jnp.asarray(padded), jnp.asarray(mask), ridge=self.ridge)
        return {"q": jnp.concatenate([factors["q"], q1], axis=0),
                "rr": jnp.concatenate([factors["rr"], rr1], axis=0),
                "mask": np.concatenate([factors["mask"], mask], axis=0),
                "r": factors["r"],
                "widths": factors["widths"] + [a_new.shape[1]]}

    def split_factors(self, factors, counts: Sequence[int]) -> list:
        """A flat roster's factor stack cut into consecutive runs of
        `counts` users, one run per group. A run keeps the roster's pad
        width even where its group is narrower: the mask makes the padded
        columns exact no-ops, and `factor_G_append` pads a newcomer to it."""
        import jax.numpy as jnp
        cuts = np.cumsum(counts)[:-1]
        q, rr = (jnp.split(factors[key], cuts) for key in ("q", "rr"))
        return [{"q": a, "rr": b, "mask": m, "r": factors["r"],
                 "widths": w.tolist()} for a, b, m, w in zip(
                     q, rr, np.split(factors["mask"], cuts),
                     np.split(np.asarray(factors["widths"]), cuts))]

    def solve_G_factors(self, factors, Z: np.ndarray) -> List[np.ndarray]:
        """All users of a group re-solved against a refreshed Z in ONE
        batched triangular solve from the cached factors."""
        import jax.numpy as jnp
        from repro.kernels.gram import ops as gram_ops
        G = _fetch(gram_ops.solve_G_from_factors(
            factors["q"], factors["rr"], jnp.asarray(Z, jnp.float32),
            jnp.asarray(factors["mask"])))
        if not np.all(np.isfinite(G)):
            bad = [b for b in range(G.shape[0])
                   if not np.all(np.isfinite(G[b]))]
            raise FloatingPointError(
                f"device least-squares produced non-finite G for users {bad}: "
                "anchor columns are (near-)collinear, which the QR path "
                "cannot handle at ridge=0 — use collab.DeviceBackend("
                "ridge=1e-3) as svd_backend, or svd_backend='host'")
        return [G[b, :w] for b, w in enumerate(factors["widths"])]


_BACKENDS = {"host": HostBackend, "device": DeviceBackend, "tpu": DeviceBackend}


def get_backend(name: str):
    """Resolve a backend name ("host" | "device" | "tpu") or pass through an
    object already implementing the CollabBackend protocol."""
    if isinstance(name, str):
        try:
            return _BACKENDS[name]()
        except KeyError:
            raise ValueError(
                f"unknown collab backend {name!r}; choose from {sorted(_BACKENDS)}")
    return name


# --------------------------------------------------------------------------
# rank-k SVD with backend dispatch (legacy single-matrix entry point)
# --------------------------------------------------------------------------

def topk_svd(A: np.ndarray, k: int, backend: str = "host"):
    """Rank-k thin SVD. Returns (U (n,k), s (k,), V (m,k))."""
    return get_backend(backend).topk_svd(A, k)


def _random_orthogonal(rng, k: int) -> np.ndarray:
    Q, R = np.linalg.qr(rng.standard_normal((k, k)))
    return Q * np.sign(np.diag(R))[None, :]


def _obfuscation(rng, s: np.ndarray, V: np.ndarray,
                 block_cols: Sequence[int], k: int) -> np.ndarray:
    """Paper's C = Σ (V_block_j')ᵀ E construction; random-orthogonal fallback
    if the selected block yields a singular / non-square matrix."""
    j = int(rng.integers(0, len(block_cols)))
    lo = int(np.sum(block_cols[:j]))
    hi = lo + int(block_cols[j])
    Vb = V[lo:hi, :]                                  # (m̃_j, k)
    if Vb.shape[0] == k:
        C = (s[:, None] * Vb.T) @ _random_orthogonal(rng, k)
        if np.linalg.cond(C) < 1e8:
            return C
    return _random_orthogonal(rng, k) * s[:, None]


# --------------------------------------------------------------------------
# protocol messages
# --------------------------------------------------------------------------

@dataclass
class GroupBasis:
    """What intra-group DC server i sends to the central FL server."""
    B: np.ndarray                       # (r, m̂_i) = U^(i) C_1^(i)


@dataclass
class CentralTarget:
    """What the central FL server returns to every DC server."""
    Z: np.ndarray                       # (r, m̂) = P C_2


def _basis_from_svd(svd, rng, block_cols: Sequence[int]) -> GroupBasis:
    U, s, V = svd
    C1 = _obfuscation(rng, s, V, block_cols, U.shape[1])
    return GroupBasis(B=U @ C1)


def intra_group_basis(anchors: List[np.ndarray], m_hat_i: int, seed: int,
                      backend: str = "host") -> GroupBasis:
    """Eq. (1) on DC server i. anchors: per-user Ã_j^(i) of shape (r, m̃_ij)."""
    rng = np.random.default_rng(seed)
    A = np.concatenate(anchors, axis=1)               # (r, Σ m̃)
    svd = get_backend(backend).topk_svd(A, m_hat_i)
    return _basis_from_svd(svd, rng, [a.shape[1] for a in anchors])


def intra_group_bases(anchor_groups: Sequence[Sequence[np.ndarray]],
                      m_hat: int, seeds: Sequence[int], backend: str = "host"
                      ) -> Tuple[List[GroupBasis], List[np.ndarray]]:
    """Eq. (1) for ALL d DC servers at once: one batched Gram+eigh launch
    per distinct width on the device backend, the serial per-group loop on
    host. Returns the bases and each group's Gram of its stacked anchors."""
    be = get_backend(backend)
    stacked = [np.concatenate(list(g), axis=1) for g in anchor_groups]
    svds, grams = be.topk_svd_many(stacked, m_hat)
    return [
        _basis_from_svd(svd, np.random.default_rng(seed),
                        [a.shape[1] for a in group])
        for svd, seed, group in zip(svds, seeds, anchor_groups)
    ], grams


def central_target(bases: List[GroupBasis], m_hat: int, seed: int,
                   backend: str = "host") -> CentralTarget:
    """Eq. (2) on the central FL server."""
    rng = np.random.default_rng(seed)
    B = np.concatenate([b.B for b in bases], axis=1)  # (r, Σ m̂_i)
    P, D, Q = get_backend(backend).topk_svd(B, m_hat)
    C2 = _obfuscation(rng, D, Q, [b.B.shape[1] for b in bases], P.shape[1])
    return CentralTarget(Z=P @ C2)


def solve_G(anchor_j: np.ndarray, Z: np.ndarray) -> np.ndarray:
    """Eq. (3): G = argmin ‖Ã_j G − Z‖_F via least squares."""
    G, *_ = np.linalg.lstsq(anchor_j, Z, rcond=None)
    return G


def solve_G_all(anchors: Sequence[np.ndarray], Z: np.ndarray,
                backend: str = "host"):
    """Eq. (3) for a flat list of users from their QR factors (the device
    backend: ONE batched QR of the padded anchors). Returns (G per user,
    the factors)."""
    be = get_backend(backend)
    factors = be.factor_G_many(anchors)
    return be.solve_G_factors(factors, Z), factors


def apply_G_all(Xs: Sequence[np.ndarray], Gs: Sequence[np.ndarray],
                backend: str = "host") -> List[np.ndarray]:
    """Step 12: collaboration representations X̂_j = X̃_j G_j for a flat list
    of users. The device backend runs ONE padded batched matmul for all
    users (zero per-user host matmuls); host is the serial float64 loop."""
    return get_backend(backend).apply_G_many(Xs, Gs)


def alignment_residual(anchor_j: np.ndarray, G: np.ndarray,
                       Z: np.ndarray) -> float:
    """Relative ‖Ã G − Z‖_F / ‖Z‖_F — 0 under Theorem-1 conditions."""
    return float(np.linalg.norm(anchor_j @ G - Z) / max(np.linalg.norm(Z), 1e-12))
