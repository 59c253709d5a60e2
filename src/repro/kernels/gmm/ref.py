"""Oracle for the grouped matrix product: each row of a group times its
group's matrix, in plain jnp."""
from __future__ import annotations

import jax.numpy as jnp


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """lhs (m, k) with its rows in group order, rhs (G, k, n), group_sizes
    (G,) int32 summing to at most m -> (m, n) in lhs's dtype: row i of
    group g is lhs[i] @ rhs[g]; rows past the last group are 0."""
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(ends, jnp.arange(m), side="right")
    out = jnp.zeros((m, rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        prod = jnp.matmul(lhs, rhs[g], preferred_element_type=jnp.float32)
        out = out + jnp.where((gid == g)[:, None], prod, 0.0)
    return out.astype(lhs.dtype)
