"""Pallas TPU kernel for the Gram reduction G = A^T A.

This is the TPU-native core of the collaboration-representation protocol
(DESIGN.md §3): instead of a tall-skinny SVD of the stacked anchor
representations à (r × m̃, r ≫ m̃) — host-bound on TPU — we reduce to the
m̃ × m̃ Gram matrix with an MXU-tiled accumulation and eigendecompose that
(core/collab.py). rank-m̂ singular pairs of à are recovered from eigh(G).

`gram_cross_batched_pallas` is the one kernel: it computes A_b^T B_b for a
whole stack of (group- or user-) matrices in a single launch — grid
(B, m/BM, n/BN, r/BR) with the batch index outermost and the reduction axis
innermost/sequential over a fp32 VMEM accumulator, so each batch element
reuses the same MXU-tiled reduction and the per-call dispatch overhead is
paid once instead of B times. BM=BN=BR=256 → blocks 3×256×256×4 = 768 KiB
VMEM. The Gram `gram_batched_pallas` is the A = B case, and `gram_pallas`
its B=1 case. Every entry is a sum over the same 256-row blocks in the same
order whatever the column tiling, so the blocks of a Gram grown by
onboarding (cross and new blocks) are bitwise the blocks a from-scratch
Gram of the wider stack computes (checked on a v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_cross_kernel(a1_ref, a2_ref, o_ref, acc_scr):
    ri = pl.program_id(3)
    nr = pl.num_programs(3)

    @pl.when(ri == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    a1 = a1_ref[0].astype(jnp.float32)        # (BR, BM)
    a2 = a2_ref[0].astype(jnp.float32)        # (BR, BN)
    acc_scr[...] += jax.lax.dot_general(
        a1, a2, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(ri == nr - 1)
    def _finish():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def _pad_to(x, br: int, bc: int):
    pad_r, pad_c = (-x.shape[1]) % br, (-x.shape[2]) % bc
    if pad_r or pad_c:
        x = jnp.pad(x, ((0, 0), (0, pad_r), (0, pad_c)))
    return x


@functools.partial(jax.jit, static_argnames=("block_m", "block_r", "interpret"))
def gram_cross_batched_pallas(a, b, *, block_m: int = 256, block_r: int = 256,
                              interpret: bool = False):
    """a: (B, r, m), b: (B, r, n) -> stacked A_b^T B_b (B, m, n) fp32, one
    launch. Pads r, m and n up to block multiples."""
    nb, r, m = a.shape
    n = b.shape[2]
    bm, bn, br = min(block_m, m), min(block_m, n), min(block_r, r)
    a, b = _pad_to(a, br, bm), _pad_to(b, br, bn)
    R, M, N = a.shape[1], a.shape[2], b.shape[2]

    out = pl.pallas_call(
        _gram_cross_kernel,
        grid=(nb, M // bm, N // bn, R // br),
        in_specs=[
            pl.BlockSpec((1, br, bm), lambda bi, mi, ni, ri: (bi, ri, mi)),
            pl.BlockSpec((1, br, bn), lambda bi, mi, ni, ri: (bi, ri, ni)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, mi, ni, ri: (bi, mi, ni)),
        out_shape=jax.ShapeDtypeStruct((nb, M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out[:, :m, :n]


def gram_batched_pallas(a, **kw):
    """a: (B, r, m) -> stacked A_b^T A_b (B, m, m) fp32, one launch."""
    return gram_cross_batched_pallas(a, a, **kw)


def gram_pallas(a, **kw):
    """a: (r, m) -> A^T A (m, m) fp32 — the B=1 case of the batched kernel."""
    return gram_batched_pallas(a[None], **kw)[0]
