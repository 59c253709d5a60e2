"""Grouped matrix product with backend dispatch: the rows of `lhs` come
sorted by group (an expert's rows together), and each group is multiplied
by its own matrix. No capacity: every row of every group is computed, and
the rows past the last group (the ones routed elsewhere) come out 0.

The Pallas path carries its own VJP (``gmm`` for the input's gradient,
``tgmm`` for the matrices') and runs member by member under ``jax.vmap``
(the FedDCL silo axis), where the kernels' grid, sized by the group sizes
of one member, cannot be batched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.gmm import ref
from repro.kernels.gmm.kernel import (grouped_matmul_pallas,
                                      grouped_matmul_t_pallas)


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


def _member_by_member(fn):
    """`fn` under vmap as a loop over the batch's members."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        return lax.map(lambda xs: f(*xs), args), True

    return f


@functools.lru_cache(maxsize=None)
def _pallas(interpret: bool):
    fwd = _member_by_member(functools.partial(
        grouped_matmul_pallas, interpret=interpret))
    fwd_t = _member_by_member(functools.partial(
        grouped_matmul_pallas, transpose_rhs=True, interpret=interpret))
    tgmm = _member_by_member(functools.partial(
        grouped_matmul_t_pallas, interpret=interpret))

    @jax.custom_vjp
    def gmm(lhs, rhs, group_sizes):
        return fwd(lhs, rhs, group_sizes)

    def gmm_fwd(lhs, rhs, group_sizes):
        return fwd(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def gmm_bwd(res, g):
        lhs, rhs, group_sizes = res
        return (fwd_t(g, rhs, group_sizes),
                tgmm(lhs, g, group_sizes).astype(rhs.dtype), None)

    gmm.defvjp(gmm_fwd, gmm_bwd)
    return gmm


def grouped_matmul(lhs, rhs, group_sizes, *, backend: str = "auto"):
    """lhs (m, k) in group order, rhs (G, k, n), group_sizes (G,) int32
    summing to at most m -> (m, n) in lhs's dtype (f32 accumulation)."""
    backend = _resolve(backend)
    group_sizes = group_sizes.astype(jnp.int32)
    if backend == "ref":
        return ref.grouped_matmul_reference(lhs, rhs, group_sizes)
    return _pallas(backend == "interpret")(lhs, rhs, group_sizes)
