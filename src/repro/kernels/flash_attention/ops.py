"""Public fused-attention entry points, model layout (B, S, H, hd).

``flash_attention``: the repo's forward-only kernel (GQA, causal, sliding
window, softcap). ``causal_attention``: trainable causal attention through
jax's Pallas splash kernels (forward, dq and dkv under their own VJP), for
q and k of one head width and v of another, as MLA has them."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "backend", "q_offset"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    backend: str = "auto"):
    """q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) -> (B, Sq, H, hd)."""
    backend = _resolve(backend)
    qh = q.swapaxes(1, 2)
    kh = k.swapaxes(1, 2)
    vh = v.swapaxes(1, 2)
    if backend == "ref":
        out = ref.mha_reference(qh, kh, vh, causal=causal, window=window,
                                softcap=softcap, q_offset=q_offset)
    else:
        out = flash_attention_pallas(
            qh, kh, vh, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, interpret=(backend == "interpret"),
        )
    return out.swapaxes(1, 2)


def causal_block(seq: int) -> int:
    """The splash kernels' q and kv block, in the forward and both backward
    kernels: 1024 for 4096 tokens beat 512 in each kernel on a v5e
    (PERF.md §6)."""
    return min(seq, 1024)


@functools.lru_cache(maxsize=None)
def _splash(heads: int, seq: int, interpret: bool):
    block = causal_block(seq)
    mask = splash.MultiHeadMask([splash.CausalMask((seq, seq))] * heads)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    with jax.ensure_compile_time_eval():       # cached: concrete mask data
        return splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                      q_seq_shards=1, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def causal_flash_attention(q, k, v, *, interpret: bool = False):
    """The splash kernels over each sequence of the batch: q pre-scaled by
    1/sqrt(qk), logits accumulated in f32 in fast memory, the key blocks
    past the causal frontier skipped. The device ops keep splash's own
    names (``splash_mha_fwd_*``, ``splash_mha_dq_*``, ``splash_mha_dkv_*``)."""
    _, seq, heads, qk = q.shape
    kernel = _splash(heads, seq, interpret)
    q = (q * (1.0 / math.sqrt(qk))).astype(q.dtype)
    out = jax.vmap(kernel)(q.swapaxes(1, 2), k.swapaxes(1, 2),
                           v.swapaxes(1, 2))
    return out.swapaxes(1, 2)


def causal_attention(q, k, v, *, backend: str = "auto"):
    """Causal attention at positions 0..S-1. q, k: (B, S, H, qk); v:
    (B, S, H, dv) -> (B, S, H, dv) in q's dtype. S a multiple of 128 and
    of ``causal_block(S)`` on the Pallas path."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.mha_reference(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                 v.swapaxes(1, 2)).swapaxes(1, 2)
    return causal_flash_attention(q, k, v, interpret=backend == "interpret")
