"""Public Gram-reduction wrappers with backend dispatch.

Single-matrix entry points (`gram`, `gram_eigh_topk`) serve the legacy
one-group-at-a-time path; the batched entry points (`gram_batched`,
`gram_eigh_topk_batched`, `solve_G_batched`) are the device-resident
collaboration engine: every group (or every user) is a slice of one stacked,
zero-padded array and the whole of FedDCL step 3 runs in a handful of jitted
calls instead of Python loops.

Padded-ragged convention (see DESIGN.md): ragged stacks are zero-padded on
the trailing column axis up to the max width. Zero columns are harmless for
the Gram route — AᵀA acquires zero rows/cols, eigh keeps them in the null
space, and the top-k eigenpairs of the real block are untouched. For least
squares they are handled explicitly via `col_mask` (see `solve_G_batched`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gram import ref
from repro.kernels.gram.kernel import (gram_batched_pallas,
                                      gram_cross_batched_pallas, gram_pallas)

# Step 3 is held to ≤1e-3 of the NumPy-f64 host backend; at TPU default
# precision an f32 matmul is one bf16 pass (~2e-3 off on the chip), so every
# matmul here asks for full f32 passes.
HIGHEST = jax.lax.Precision.HIGHEST


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


@functools.partial(jax.jit, static_argnames=("backend",))
def gram(a, *, backend: str = "auto"):
    """a: (r, m) -> A^T A in fp32."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.gram_reference(a)
    return gram_pallas(a, interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend",))
def gram_batched(a, *, backend: str = "auto"):
    """a: (B, r, m) -> stacked A_b^T A_b (B, m, m) fp32 in ONE dispatch."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.gram_batched_reference(a)
    return gram_batched_pallas(a, interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend",))
def gram_cross_batched(a, b, *, backend: str = "auto"):
    """a: (B, r, m), b: (B, r, n) -> stacked A_b^T B_b (B, m, n) fp32 in ONE
    dispatch — the off-diagonal blocks of a Gram grown by new columns."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.gram_cross_batched_reference(a, b)
    return gram_cross_batched_pallas(a, b, interpret=(backend == "interpret"))


def gram_eigh_topk(a, k: int, *, backend: str = "auto"):
    """Rank-k left singular pairs of a (r, m) via the Gram route:
    eigh(AᵀA) -> right vectors V, singular values s; U = A V / s.

    Returns (U (r,k), s (k,), V (m,k)) — the B=1 case of the batched
    recovery. Matches jnp.linalg.svd up to sign for well-separated
    spectra (tested).
    """
    U, s, V = gram_eigh_topk_batched(a[None], k, backend=backend)
    return U[0], s[0], V[0]


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def gram_eigh_topk_batched(a, k: int, *, backend: str = "auto"):
    """Batched rank-k singular recovery: a (B, r, m) -> (U (B,r,k),
    s (B,k), V (B,m,k)) — one batched Gram reduction + one batched eigh.

    Zero-padded columns contribute zero eigenvalues and never reach the
    top-k slots as long as k ≤ rank of the real block.
    """
    g = gram_batched(a, backend=backend)              # (B, m, m)
    return eigh_topk_recover_batched(g, a, k)


@functools.partial(jax.jit, static_argnames=("k",))
def eigh_topk_recover_batched(g, a, k: int):
    """Rank-k singular recovery from a PRECOMPUTED Gram stack: the shared
    tail of `gram_eigh_topk_batched` and the incremental-onboarding path,
    where g was maintained by `gram_append_blocked` instead of being
    reduced from scratch.

    g: (B, m, m) Gram stack AᵀA;  a: (B, r, m) the matrices themselves
    (needed to recover the left factors U = A V / s).
    """
    evals, evecs = jnp.linalg.eigh(g)                 # ascending, batched
    evals = evals[:, ::-1][:, :k]
    V = evecs[:, :, ::-1][:, :, :k]                   # (B, m, k)
    s = jnp.sqrt(jnp.maximum(evals, 0.0))             # (B, k)
    U = jnp.einsum("brm,bmk->brk", a.astype(jnp.float32), V,
                   precision=HIGHEST)
    U = U / jnp.maximum(s, 1e-12)[:, None, :]
    return U, s, V


@jax.jit
def gram_append_blocked(g, a_old, a_new):
    """Blocked incremental Gram update for tenant onboarding: given the
    maintained Gram g = A_oldᵀA_old and the w new columns a_new joining the
    stack, return Gram([A_old A_new]) computing ONLY the cross and new
    blocks —

        [[ g          A_oldᵀA_new ]
         [ A_newᵀA_old A_newᵀA_new ]]

    O(r·W·w) work instead of the O(r·(W+w)²) full reduction, batched over
    a leading group axis. Every new block goes through the Gram kernel, so the
    grown Gram is the one a from-scratch reduction of [A_old A_new] gives.

    g: (B, W, W);  a_old: (B, r, W);  a_new: (B, r, w) -> (B, W+w, W+w).
    """
    # the lower block is reduced itself, not transposed: the kernel's Gram
    # is not bitwise symmetric, and eigh reads both triangles
    top = jnp.concatenate([g.astype(jnp.float32),
                           gram_cross_batched(a_old, a_new)], axis=2)
    bot = jnp.concatenate([gram_cross_batched(a_new, a_old),
                           gram_batched(a_new)], axis=2)
    return jnp.concatenate([top, bot], axis=1)


@jax.jit
def apply_G_batched(x, g):
    """Batched per-user collaboration representations X̂_j = X̃_j G_j for a
    whole stack of users in ONE device matmul.

    x: (U, n_max, m̃_max) intermediate representations, zero-padded on both
       the sample axis (ragged n_j) and the column axis (ragged m̃_j)
    g: (U, m̃_max, m̂) per-user G, zero-padded on the row axis

    Padded columns of x only ever meet zero rows of g, so the real block of
    the product is EXACT; padded sample rows produce garbage that callers
    slice away. No masks needed.
    """
    return jnp.einsum("unm,umh->unh", x.astype(jnp.float32),
                      g.astype(jnp.float32), precision=HIGHEST)


@jax.jit
def solve_G_batched(a, z, col_mask=None, ridge: float = 0.0):
    """Batched eq. (3): G_b = argmin ‖A_b G − Z_b‖_F for a whole stack of
    users in one jitted QR solve.

    a:        (B, r, m_max) anchors, zero-padded on the column axis
    z:        (r, m_hat) shared target, or (B, r, m_hat) per-batch targets
    col_mask: (B, m_max) with True on REAL columns (None = all real)
    ridge:    relative Tikhonov strength (see below); 0.0 = exact lstsq

    Returns G (B, m_max, m_hat) with exact zero rows at padded positions.

    Padded columns would make the QR factor singular, so the system is
    augmented with m_max extra rows holding diag(1 − mask): the objective
    becomes ‖A_real G_real − Z‖² + Σ_padded G_k², whose minimiser is the
    plain least-squares solution on real columns and 0 on padded rows
    (cross terms vanish because padded columns of A are exactly zero).
    Unlike normal equations this does not square the condition number.

    QR without pivoting requires the REAL columns to be full rank — the
    protocol guarantees this generically (anchors are random full-rank
    matrices through injective maps), but exactly collinear anchor columns
    would blow the triangular solve up where host lstsq returns the bounded
    min-norm solution. For such degenerate inputs pass ridge > 0 (e.g.
    1e-3): the real-column augmentation rows become
    ridge · max_colnorm(A_b) · I, bounding ‖G‖ by ~‖Z‖/(ridge·scale) at
    the cost of an O(ridge²·κ²) relative perturbation on well-conditioned
    directions.
    """
    q, rr = solve_G_factor_batched(a, col_mask, ridge=ridge)
    return solve_G_from_factors(q, rr, z, col_mask)


@jax.jit
def solve_G_factor_batched(a, col_mask=None, ridge: float = 0.0):
    """Factor half of `solve_G_batched`: the batched reduced QR of the
    augmented anchor stacks. Returns (q (B, r+m_max, m_max),
    rr (B, m_max, m_max)).

    The factors depend only on the anchors, never on the target Z — the
    incremental-onboarding path caches them per tenant so a Z refresh
    (a new tenant shifted the central target) re-solves every G with
    `solve_G_from_factors` alone: one triangular solve per tenant, zero
    re-factorizations.
    """
    a = a.astype(jnp.float32)
    b, r, m_max = a.shape
    if col_mask is None:
        col_mask = jnp.ones((b, m_max), dtype=bool)
    maskf = col_mask.astype(jnp.float32)              # (B, m_max)
    scale = jnp.sqrt(jnp.max(jnp.sum(a * a, axis=1), axis=-1))  # (B,)
    diag = (1.0 - maskf) + maskf * (ridge * scale[:, None])
    aug = diag[:, :, None] * jnp.eye(m_max, dtype=jnp.float32)[None]
    a_aug = jnp.concatenate([a, aug], axis=1)         # (B, r+m_max, m_max)
    return jnp.linalg.qr(a_aug)                       # reduced, batched


@jax.jit
def solve_G_from_factors(q, rr, z, col_mask=None):
    """Apply half of `solve_G_batched`: G = R⁻¹ Qᵀ [Z; 0] from cached QR
    factors. z: (r, m_hat) shared target or (B, r, m_hat) per-batch."""
    b, _, m_max = rr.shape
    if z.ndim == 2:
        z = jnp.broadcast_to(z[None], (b,) + z.shape)
    z = z.astype(jnp.float32)
    if col_mask is None:
        col_mask = jnp.ones((b, m_max), dtype=bool)
    z_aug = jnp.concatenate(
        [z, jnp.zeros((b, m_max, z.shape[-1]), z.dtype)], axis=1)
    rhs = jnp.einsum("bnm,bnh->bmh", q, z_aug, precision=HIGHEST)
    G = jax.scipy.linalg.solve_triangular(rr, rhs, lower=False)
    return G * col_mask[:, :, None]
