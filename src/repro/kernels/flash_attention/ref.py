"""Pure-jnp oracle for fused attention (GQA + causal + sliding window +
logit softcap). Layout: q (B, H, Sq, hd); k/v (B, KV, Sk, hd)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def mha_reference(q, k, v, *, causal: bool = True, window: int = 0,
                  softcap: float = 0.0, q_offset: int = 0) -> jnp.ndarray:
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, Sq, hd)
    logits = jnp.einsum("bhgqk,bhsk->bhgqs", qg, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    if softcap and softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window and window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bhgqs,bhsk->bhgqk", probs, v)
    return ctx.reshape(B, H, Sq, v.shape[-1])
