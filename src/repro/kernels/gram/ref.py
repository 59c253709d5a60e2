"""Oracle for the Gram reduction: G = A^T A in fp32."""
from __future__ import annotations

import jax.numpy as jnp


def gram_reference(a: jnp.ndarray) -> jnp.ndarray:
    """a: (r, m) -> (m, m) fp32."""
    af = a.astype(jnp.float32)
    return af.T @ af


def gram_batched_reference(a: jnp.ndarray) -> jnp.ndarray:
    """a: (B, r, m) -> (B, m, m) fp32."""
    return gram_cross_batched_reference(a, a)


def gram_cross_batched_reference(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a: (B, r, m), b: (B, r, n) -> (B, m, n) fp32."""
    return jnp.einsum("brm,brn->bmn", a.astype(jnp.float32),
                      b.astype(jnp.float32))
