"""The TPU grouped matrix products: jax's Pallas megablox kernels (``gmm``
for rows × their group's matrix, ``tgmm`` for the per-group products of
two row blocks), each traced inside a jit whose name the device ops carry
(``grouped_matmul_pallas``, ``grouped_matmul_t_pallas``), so the trace
reduction finds them."""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the module (the package's own `gmm` attribute is its custom-VJP function);
# its kernels are called unjitted so that they take this module's op names
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")
_gmm = megablox.gmm.__wrapped__
_tgmm = megablox.tgmm.__wrapped__

ROW_TILE = 512


def _tile(x: int, most: int) -> int:
    """The fewest equal tiles of `x` of at most `most` when they are
    multiples of 128; the whole of `x` when it fits; else `most` (the
    kernel masks the remainder)."""
    if x <= most:
        return x
    n = -(-x // most)
    return x // n if x % n == 0 and (x // n) % 128 == 0 else most


def tiling(m: int, k: int, n: int, most: int):
    """(rows, contraction, output) tile sizes: 512 rows, at most `most` of
    the two widths. Under the v5e's 16 MiB of scoped VMEM: 1536 for
    ``gmm`` (13.5 MiB at 2048 × 2816 and 1408 × 2048), 1024 for ``tgmm``,
    whose output block is its f32 accumulator too (12 MiB)."""
    return min(ROW_TILE, m), _tile(k, most), _tile(n, most)


def _pad_rows(x, tm: int):
    pad = (-x.shape[0]) % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def grouped_matmul_pallas(lhs, rhs, group_sizes, *, transpose_rhs=False,
                          interpret=False):
    """lhs (m, k), rhs (G, k, n) — (G, n, k) with `transpose_rhs` — ->
    (m, n) in lhs's dtype; rows past the last group are 0."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling(m, k, n, 1536)
    out = _gmm(_pad_rows(lhs, tm), rhs, group_sizes,
               preferred_element_type=lhs.dtype, tiling=(tm, tk, tn),
               transpose_rhs=transpose_rhs, interpret=interpret)[:m]
    held = jnp.arange(m) < jnp.sum(group_sizes)
    return jnp.where(held[:, None], out, jnp.zeros((), out.dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul_t_pallas(lhs, grad, group_sizes, *, interpret=False):
    """lhs (m, k), grad (m, n) -> (G, k, n): each group's lhsᵀ · grad over
    its rows, in lhs's dtype (f32 accumulation)."""
    m, k = lhs.shape
    tm, tk, tn = tiling(m, k, grad.shape[1], 1024)
    return _tgmm(_pad_rows(lhs, tm).swapaxes(0, 1), _pad_rows(grad, tm),
                 group_sizes, preferred_element_type=lhs.dtype,
                 tiling=(tm, tk, tn), interpret=interpret)
