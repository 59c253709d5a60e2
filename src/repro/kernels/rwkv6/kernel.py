"""Pallas TPU kernel for the chunked WKV6 recurrence.

Grid: (B*H, S/L) — the chunk axis is sequential on TPU, so the recurrent
state lives in a VMEM scratch buffer that persists across chunk steps for a
fixed (batch, head) program. Within a chunk the pairwise decay is factored
into two (L, K) operands and hits the MXU as an (L,K)@(K,L) matmul.

VMEM budget per program (L=16, K=V=64, fp32):
  r,k,v,lw blocks: 4 × L×K×4   =  16 KiB
  state scratch:   K×V×4       =  16 KiB
  A matrix:        L×L×4       =   1 KiB
comfortably inside the ~16 MiB VMEM of a TPU core; block shapes are padded
to the fp32 (8, 128) tile by Pallas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref):
    chunk_idx = pl.program_id(1)

    @pl.when(chunk_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # (L, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # (L, V)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (1, K)

    L = r.shape[0]
    idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # inclusive log-decay cumsum as a lower-triangular matmul (Mosaic has
    # no cumsum); f32-exact passes keep the decays exp(±c) accurate
    tri = (idx >= jdx).astype(jnp.float32)
    c = jax.lax.dot_general(tri, lw, (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    cs = c - lw                               # exclusive
    r_t = r * jnp.exp(cs)
    k_t = k * jnp.exp(-c)

    A = jax.lax.dot_general(
        r_t, k_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                         # (L, L)
    A = jnp.where(idx > jdx, A, 0.0)
    diag = jnp.sum(r * k * u, axis=-1)                   # (L,)

    state = state_ref[...]                    # (K, V)
    y = (
        jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + diag[:, None] * v
        + jax.lax.dot_general(r_t, state, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    )
    o_ref[0] = y.astype(o_ref.dtype)

    last = c[L - 1:L, :]                      # (1, K) chunk-total decay
    k_end = k * jnp.exp(last - c)
    # state rows scale by exp(last): a diagonal matmul, since Mosaic has no
    # cheap (1, K) -> (K, 1) relayout
    K = state.shape[0]
    decay = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
             ).astype(jnp.float32) * jnp.exp(last)
    state_ref[...] = jax.lax.dot_general(
        decay, state, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        k_end, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, log_w, u, *, chunk: int = 16, interpret: bool = False):
    """r/k/log_w: (BH, S, K); v: (BH, S, V); u: (BH, K). -> fp32 (BH, S, V).

    u travels as (BH, 1, K) so its block's last two dims equal the array's
    — Mosaic refuses a (1, K) block of a 2-D (BH, K) array."""
    BH, S, K = r.shape
    V = v.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, f"seq {S} % chunk {L} != 0"
    grid = (BH, S // L)

    seq_spec = pl.BlockSpec((1, L, K), lambda g, c: (g, c, 0))
    val_spec = pl.BlockSpec((1, L, V), lambda g, c: (g, c, 0))
    u_spec = pl.BlockSpec((1, 1, K), lambda g, c: (g, 0, 0))

    return pl.pallas_call(
        _wkv6_kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, val_spec, seq_spec, u_spec],
        out_specs=val_spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, V), jnp.float32),
        # persistent recurrent state across the sequential chunk axis
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(r, k, v, log_w, u[:, None, :])
