"""Layer library: norms, RoPE, attention (GQA / sliding-window / softcap /
qk-norm), MLA, SwiGLU MLP, MoE, RWKV6 time/channel mix, Mamba2 (SSD).

Functional style: ``init_*`` builds a dict pytree of parameters,
``apply_*`` consumes it. No framework dependency (flax is not installed).

Dtype convention: params live in ``param_dtype``; activations are computed in
``compute_dtype`` (bf16 on TPU) with fp32 accumulation where it matters
(softmax, norms, recurrent states, router logits).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, SSMConfig
from repro.kernels.flash_attention import ops as fa_ops
from repro.shardingx.constrain import constrain

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def _dense_init(key, shape, in_axis_size, dtype):
    """Truncated-normal fan-in init (matches common LLM init scales)."""
    std = 1.0 / math.sqrt(max(in_axis_size, 1))
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * std).astype(dtype)


def _zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def _ones(shape, dtype):
    return jnp.ones(shape, dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": _ones((d,), dtype)}


def apply_rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5,
                  zero_centered: bool = False) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * lax.rsqrt(var + eps)
    scale = p["scale"].astype(jnp.float32)
    if zero_centered:           # gemma-style (1 + scale)
        scale = 1.0 + scale
    return (xf * scale).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_angles(positions: jnp.ndarray, head_dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (...,) int -> sin/cos of shape (..., head_dim//2), fp32."""
    half = head_dim // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray) -> jnp.ndarray:
    """x: (..., head_dim); sin/cos broadcastable to (..., head_dim//2).

    Rotates pairs (x[..., :half], x[..., half:]) — "half" layout.
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _bcast_rope(sin: jnp.ndarray, cos: jnp.ndarray):
    """(B, S, half) -> (B, S, 1, half) to broadcast over heads."""
    return sin[..., None, :], cos[..., None, :]


# --------------------------------------------------------------------------
# Attention (GQA, sliding window, softcap, qk-norm) — training/prefill path
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, H, hd), d, dtype),
        "wk": _dense_init(ks[1], (d, KV, hd), d, dtype),
        "wv": _dense_init(ks[2], (d, KV, hd), d, dtype),
        "wo": _dense_init(ks[3], (H, hd, d), H * hd, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _softcap(logits: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap and cap > 0.0:
        return jnp.tanh(logits / cap) * cap
    return logits


def attention_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, *,
                   is_local, window: int) -> jnp.ndarray:
    """Boolean (broadcast) mask: True = attend. q_pos (..., Sq), k_pos (..., Sk).

    ``is_local`` may be a traced scalar bool (gemma2 alternating layers under
    scan) — resolved with jnp.where so a single program serves both kinds.
    """
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    causal = k <= q
    local = causal & (k > q - window)
    return jnp.where(is_local, local, causal)


def multi_head_attention(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                         positions: jnp.ndarray,
                         is_local=False,
                         use_pallas: bool = False,
                         return_kv: bool = False):
    """Full-sequence attention. x: (B, S, d); positions: (B, S).
    With return_kv, also returns the rope'd (k, v) for prefill cache fill."""
    B, S, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    q = constrain(q, "batch", None, "model", None)
    if cfg.qk_norm:
        q = apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(p["k_norm"], k, cfg.norm_eps)
    sin, cos = rope_angles(positions, hd, cfg.rope_theta)
    sin_b, cos_b = _bcast_rope(sin, cos)
    q = apply_rope(q, sin_b, cos_b)
    k = apply_rope(k, sin_b, cos_b)

    if use_pallas:
        ctx = fa_ops.flash_attention(
            q, k, v, causal=True,
            window=cfg.sliding_window if bool(is_local) else 0,
            softcap=cfg.attn_logit_softcap,
        )
    else:
        ctx = sdpa(
            q, k, v,
            q_pos=positions, k_pos=positions,
            is_local=is_local, window=cfg.sliding_window,
            softcap=cfg.attn_logit_softcap,
        )
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(x.dtype))
    if return_kv:
        return out, (k, v)
    return out


QCHUNK_THRESHOLD = 4096     # q-chunk full-sequence attention from this Sq up
QCHUNK = 1024               # query-block size for the chunked XLA path

# ---------------------------------------------------------------------------
# unroll mode: the dry-run traces with statically unrolled inner loops so
# XLA cost analysis (which counts while-loop bodies exactly once) reports
# honest per-step FLOPs/bytes. Production/tests keep lax.scan.
# ---------------------------------------------------------------------------
import contextlib

_UNROLL = False


def unroll_mode() -> bool:
    return _UNROLL


@contextlib.contextmanager
def unrolled(enable: bool = True):
    global _UNROLL
    old = _UNROLL
    _UNROLL = enable
    try:
        yield
    finally:
        _UNROLL = old


def maybe_scan(f, carry, xs):
    """lax.scan, or an unrolled python loop under `unrolled()` tracing."""
    if not _UNROLL:
        return lax.scan(f, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = f(carry, xi)
        ys.append(y)
    ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return carry, ys


def sdpa_qchunked(q, k, v, *, q_pos, k_pos, is_local, window, softcap,
                  chunk: int = QCHUNK) -> jnp.ndarray:
    """Query-block-chunked attention: never materializes the full Sq×Sk logit
    matrix (the XLA-path analogue of the Pallas flash kernel's VMEM tiling —
    peak temp drops from O(Sq·Sk) to O(chunk·Sk) per head). Each chunk is
    recomputed in the backward pass, so autodiff keeps no chunk's
    probabilities either."""
    B, Sq, H, hd = q.shape
    if Sq % chunk:
        return sdpa_reference(q, k, v, q_pos=q_pos, k_pos=k_pos,
                              is_local=is_local, window=window, softcap=softcap)
    nq = Sq // chunk
    qs = q.reshape(B, nq, chunk, H, hd).swapaxes(0, 1)        # (nq,B,c,H,hd)
    ps = q_pos.reshape(B, nq, chunk).swapaxes(0, 1)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(_, xs):
        qc, pc = xs
        ctx = sdpa_reference(qc, k, v, q_pos=pc, k_pos=k_pos,
                             is_local=is_local, window=window, softcap=softcap)
        return None, ctx

    _, out = maybe_scan(body, None, (qs, ps))
    return out.swapaxes(0, 1).reshape(B, Sq, H, v.shape[-1])


def sdpa(q, k, v, *, q_pos, k_pos, is_local, window, softcap) -> jnp.ndarray:
    if q.shape[1] >= QCHUNK_THRESHOLD:
        return sdpa_qchunked(q, k, v, q_pos=q_pos, k_pos=k_pos,
                             is_local=is_local, window=window, softcap=softcap)
    return sdpa_reference(q, k, v, q_pos=q_pos, k_pos=k_pos, is_local=is_local,
                          window=window, softcap=softcap)


def causal_flash_applies(seq: int, *, is_local, attn_variant: str,
                         softcap: float) -> bool:
    """Whether full-sequence attention over positions 0..seq-1 runs as the
    fused causal kernel (``fa_ops.causal_attention``) in place of ``sdpa``:
    on a TPU, at a length the kernel's blocks tile, with no softcap and no
    sliding window. A layer flag traced under the layer scan comes from
    ``backbone._local_flags``, false on every layer of the "full" variant."""
    if jax.default_backend() != "tpu" or softcap:
        return False
    if seq < 128 or seq % 128 or seq % fa_ops.causal_block(seq):
        return False
    if isinstance(is_local, jax.core.Tracer):
        return attn_variant == "full"
    return not bool(is_local)


def sdpa_reference(q, k, v, *, q_pos, k_pos, is_local, window, softcap) -> jnp.ndarray:
    """Masked GQA attention, fp32 softmax. q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd).

    KV heads are expanded to the full H so the Sq×Sk logit tensor carries a
    clean (batch, model-on-heads) sharding — GQA head counts (8, 4, 2) are
    rarely divisible by the 16-wide model axis, but H always is here. The
    expansion costs O(B·Sk·H·hd) bytes, negligible against the logits."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32)
    logits = constrain(logits, "batch", "model", None, None)
    logits = logits / math.sqrt(hd)
    logits = _softcap(logits, softcap)
    mask = attention_mask(q_pos, k_pos, is_local=is_local, window=window)  # (B,Sq,Sk)
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    return constrain(ctx, "batch", None, "model", None)


# --------------------------------------------------------------------------
# Attention — single-token decode against a ring-buffer KV cache
# --------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int, num_layers: int,
                  dtype) -> Params:
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((num_layers, batch, cache_len, KV, hd), dtype),
        "v": jnp.zeros((num_layers, batch, cache_len, KV, hd), dtype),
        # absolute position stored per slot; -1 = empty
        "pos": jnp.full((num_layers, batch, cache_len), -1, jnp.int32),
    }


def decode_attention(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     cache_pos: jnp.ndarray, cur_pos: jnp.ndarray,
                     is_local=False) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """One-token decode. x: (B, 1, d); cache_k/v: (B, C, KV, hd);
    cache_pos: (B, C) absolute positions; cur_pos: (B,) int32.

    Returns (out (B,1,d), updated (k, v, pos)). Ring-buffer write at
    cur_pos % C, so a sliding-window cache uses C = window.
    """
    B, _, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    C = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(p["k_norm"], k, cfg.norm_eps)
    sin, cos = rope_angles(cur_pos[:, None], hd, cfg.rope_theta)  # (B,1,half)
    sin_b, cos_b = _bcast_rope(sin, cos)
    q = apply_rope(q, sin_b, cos_b)
    k = apply_rope(k, sin_b, cos_b)

    slot = (cur_pos % C).astype(jnp.int32)                      # (B,)
    bidx = jnp.arange(B)
    new_k = cache_k.at[bidx, slot].set(k[:, 0].astype(cache_k.dtype))
    new_v = cache_v.at[bidx, slot].set(v[:, 0].astype(cache_v.dtype))
    new_pos = cache_pos.at[bidx, slot].set(cur_pos.astype(jnp.int32))

    G = H // KV
    valid = (new_pos >= 0) & (new_pos <= cur_pos[:, None])       # (B, C)
    window_ok = jnp.where(is_local, new_pos > cur_pos[:, None] - cfg.sliding_window, True)
    mask = valid & window_ok
    if cfg.decode_expand_kv:
        # hillclimbed decode: expand kv heads so logits shard heads over the
        # model axis (cache replicated over model — no per-layer all-reduce)
        kf = jnp.repeat(new_k.astype(q.dtype), G, axis=2)        # (B,C,H,hd)
        vf = jnp.repeat(new_v.astype(q.dtype), G, axis=2)
        qh = constrain(q[:, 0], "batch", "model", None)          # (B,H,hd)
        logits = jnp.einsum("bhk,bchk->bhc", qh, kf).astype(jnp.float32)
        logits = constrain(logits, "batch", "model", None)
        logits = _softcap(logits / math.sqrt(hd), cfg.attn_logit_softcap)
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        ctx = jnp.einsum("bhc,bchk->bhk", probs, vf)[:, None]    # (B,1,H,hd)
    else:
        qg = q.reshape(B, KV, G, hd)                             # Sq==1 squeezed
        logits = jnp.einsum("bhgk,bchk->bhgc", qg, new_k.astype(q.dtype)).astype(jnp.float32)
        logits = _softcap(logits / math.sqrt(hd), cfg.attn_logit_softcap)
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        ctx = jnp.einsum("bhgc,bchk->bhgk", probs, new_v.astype(q.dtype))
        ctx = ctx.reshape(B, 1, H, hd)
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(x.dtype))
    return out, (new_k, new_v, new_pos)


# --------------------------------------------------------------------------
# MLA — DeepSeek multi-head latent attention
# --------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    if m.q_lora_rank:
        p = {
            "w_dq": _dense_init(ks[0], (d, m.q_lora_rank), d, dtype),
            "q_norm": init_rmsnorm(m.q_lora_rank, dtype),
            "w_uq": _dense_init(ks[1], (m.q_lora_rank, H, qk), m.q_lora_rank, dtype),
        }
    else:
        p = {"w_q": _dense_init(ks[0], (d, H, qk), d, dtype)}
    return dict(p, **{
        "w_dkv": _dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), d, dtype),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, dtype),
        "w_uk": _dense_init(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim), m.kv_lora_rank, dtype),
        "w_uv": _dense_init(ks[4], (m.kv_lora_rank, H, m.v_head_dim), m.kv_lora_rank, dtype),
        "wo": _dense_init(ks[5], (H, m.v_head_dim, d), H * m.v_head_dim, dtype),
    })


def _mla_query(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """(B, S, d) -> (B, S, H, nope + rope): through the q-LoRA, or directly
    where ``q_lora_rank`` is 0."""
    if not cfg.mla.q_lora_rank:
        return jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(x.dtype))
    cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(x.dtype))
    cq = apply_rmsnorm(p["q_norm"], cq, cfg.norm_eps)
    return jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"].astype(x.dtype))


def mla_attention(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                  positions: jnp.ndarray, is_local=False,
                  return_kv: bool = False):
    """Training/prefill MLA (naive expanded form). x: (B, S, d).
    With return_kv, also returns the latent cache entries (ckv, k_rope).

    On a TPU the attention core is the fused causal kernel wherever
    ``causal_flash_applies``; its mask is exact because every full-sequence
    caller takes ``positions`` from ``backbone.embed_inputs``, arange(S)."""
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.num_heads
    q = _mla_query(p, x, cfg)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]

    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype))
    ckv, k_rope = ckv_full[..., : m.kv_lora_rank], ckv_full[..., m.kv_lora_rank:]
    ckv = apply_rmsnorm(p["kv_norm"], ckv, cfg.norm_eps)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"].astype(x.dtype))

    sin, cos = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    sin_b, cos_b = _bcast_rope(sin, cos)
    q_rope = apply_rope(q_rope, sin_b, cos_b)
    k_rope = apply_rope(k_rope[:, :, None, :], sin_b, cos_b)     # (B,S,1,rope)

    # treat (nope ‖ rope) as one effective head dim and reuse the (q-chunked)
    # sdpa path — k_rope is shared across heads (broadcast as a 1-kv-head
    # suffix is wrong for GQA grouping, so concatenate explicitly).
    q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)           # (B,S,H,nope+rope)
    k_eff = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_head_dim))], axis=-1)
    q_eff = constrain(q_eff, "batch", None, "model", None)
    k_eff = constrain(k_eff, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)
    # both paths scale by 1/sqrt(nope + rope), MLA's scale
    if causal_flash_applies(S, is_local=is_local,
                            attn_variant=cfg.attn_variant, softcap=0.0):
        ctx = fa_ops.causal_attention(q_eff, k_eff, v)
    else:
        ctx = sdpa(q_eff, k_eff, v, q_pos=positions, k_pos=positions,
                   is_local=is_local, window=cfg.sliding_window, softcap=0.0)
    out = jnp.einsum("bqhk,hkd->bqd", ctx, p["wo"].astype(x.dtype))
    if return_kv:
        return out, (ckv, k_rope[:, :, 0, :])
    return out


def init_mla_cache(cfg: ModelConfig, batch: int, cache_len: int, num_layers: int, dtype) -> Params:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((num_layers, batch, cache_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((num_layers, batch, cache_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((num_layers, batch, cache_len), -1, jnp.int32),
    }


def mla_decode(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
               cache_ckv, cache_krope, cache_pos, cur_pos,
               is_local=False) -> Tuple[jnp.ndarray, Tuple]:
    """Absorbed-weight MLA decode: scores against the compressed cache —
    the latent cache (kv_lora + rope dims per token) is the MLA memory win.
    """
    m = cfg.mla
    B, _, d = x.shape
    H = cfg.num_heads
    C = cache_ckv.shape[1]
    q = _mla_query(p, x, cfg)[:, 0]                              # (B,H,qk)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]

    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype))[:, 0]
    ckv, k_rope = ckv_full[..., : m.kv_lora_rank], ckv_full[..., m.kv_lora_rank:]
    ckv = apply_rmsnorm(p["kv_norm"], ckv, cfg.norm_eps)

    sin, cos = rope_angles(cur_pos[:, None], m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin[:, 0][:, None, :], cos[:, 0][:, None, :])
    k_rope = apply_rope(k_rope[:, None, :], sin, cos)[:, 0]

    slot = (cur_pos % C).astype(jnp.int32)
    bidx = jnp.arange(B)
    new_ckv = cache_ckv.at[bidx, slot].set(ckv.astype(cache_ckv.dtype))
    new_krope = cache_krope.at[bidx, slot].set(k_rope.astype(cache_krope.dtype))
    new_pos = cache_pos.at[bidx, slot].set(cur_pos.astype(jnp.int32))

    # absorb: q_eff[b,h,r] = sum_k q_nope[b,h,k] * w_uk[r,h,k]
    q_eff = jnp.einsum("bhk,rhk->bhr", q_nope, p["w_uk"].astype(x.dtype))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    logits = (
        jnp.einsum("bhr,bcr->bhc", q_eff, new_ckv.astype(x.dtype))
        + jnp.einsum("bhk,bck->bhc", q_rope, new_krope.astype(x.dtype))
    ).astype(jnp.float32) * scale
    valid = (new_pos >= 0) & (new_pos <= cur_pos[:, None])
    window_ok = jnp.where(is_local, new_pos > cur_pos[:, None] - cfg.sliding_window, True)
    logits = jnp.where((valid & window_ok)[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhc,bcr->bhr", probs, new_ckv.astype(x.dtype))   # latent ctx
    out_h = jnp.einsum("bhr,rhk->bhk", ctx, p["w_uv"].astype(x.dtype))  # (B,H,v)
    out = jnp.einsum("bhk,hkd->bd", out_h, p["wo"].astype(x.dtype))[:, None]
    return out, (new_ckv, new_krope, new_pos)


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d, d_ff), d, dtype),
        "w_up": _dense_init(ks[1], (d, d_ff), d, dtype),
        "w_down": _dense_init(ks[2], (d_ff, d), d_ff, dtype),
    }


def apply_mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, p["w_up"].astype(x.dtype))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, p["w_down"].astype(x.dtype))


# --------------------------------------------------------------------------
# Mixture of Experts
# --------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    """The router over all E experts; the weights of the held ones only."""
    mo = cfg.moe
    d, E, f = cfg.d_model, mo.num_experts, mo.d_ff_expert
    held = mo.num_held
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, E), d, jnp.float32),   # router in fp32
        "w_gate": _dense_init(ks[1], (held, d, f), d, dtype),
        "w_up": _dense_init(ks[2], (held, d, f), d, dtype),
        "w_down": _dense_init(ks[3], (held, f, d), f, dtype),
    }
    if mo.router == "sigmoid":
        p["router_bias"] = _zeros((E,), jnp.float32)            # ds-v3 aux-free bias
    if mo.num_shared_experts:
        p["shared"] = init_mlp(ks[4], d, mo.d_ff_shared * mo.num_shared_experts, dtype)
    return p


def _router_probs(p: Params, x2d: jnp.ndarray, mo: MoEConfig,
                  precision=None):
    """x2d: (T, d) -> (gates (T,k), idx (T,k), probs_full (T,E) fp32)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"],
                        precision=precision)
    if mo.router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        sel = probs + p["router_bias"][None, :]                 # bias affects selection only
        _, idx = lax.top_k(sel, mo.top_k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
        gates = gates * mo.routed_scaling
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = lax.top_k(probs, mo.top_k)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
    return gates, idx, probs


def moe_aux_loss(probs: jnp.ndarray, idx: jnp.ndarray, mo: MoEConfig) -> jnp.ndarray:
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    E = mo.num_experts
    T = probs.shape[0]
    counts = jnp.zeros((E,), jnp.float32)
    one_hot = jax.nn.one_hot(idx, E, dtype=jnp.float32)          # (T, k, E)
    counts = one_hot.sum(axis=(0, 1))
    f = counts / (T * mo.top_k)
    P = probs.mean(axis=0)
    return E * jnp.sum(f * P)


def apply_moe_dense(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense (all-experts) path for tiny smoke configs: every token through
    every expert, weighted by the (top-k masked) gate. O(T·E·d·f) FLOPs."""
    mo = cfg.moe
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    gates, idx, probs = _router_probs(p, x2d, mo)
    E = mo.num_experts
    dense_gates = jnp.zeros((x2d.shape[0], E), jnp.float32)
    dense_gates = dense_gates.at[jnp.arange(x2d.shape[0])[:, None], idx].add(gates)
    g = jnp.einsum("td,edf->tef", x2d, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", x2d, p["w_up"].astype(x.dtype))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"].astype(x.dtype))
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), dense_gates).astype(x.dtype)
    if mo.num_shared_experts:
        out = out + apply_mlp(p["shared"], x2d)
    return out.reshape(B, S, d), moe_aux_loss(probs, idx, mo)


def apply_moe_gspmd(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-based dispatch via scatter/gather (no one-hot matmuls, so
    cost_analysis FLOPs stay honest ≈ active-expert FLOPs × capacity_factor).

    Runs under plain jit; GSPMD partitions the (E, C, d) buffers over the
    mesh. Tokens above capacity are dropped (standard Switch semantics).
    """
    mo = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = mo.num_experts, mo.top_k
    x2d = x.reshape(T, d)
    gates, idx, probs = _router_probs(p, x2d, mo)

    cap = max(int(mo.capacity_factor * T * k / E), 1)

    # position of each (token, slot) within its expert queue, via a stable
    # sort by expert id (earliest-token capacity priority). NOTE: a (T·k, E)
    # one-hot cumsum would lower to an O((T·k)²·E) reduce-window — the sort
    # is both honest in cost analysis and cheaper on hardware.
    flat_e = idx.reshape(-1)                                     # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))           # first row per expert
    pos_sorted = jnp.arange(T * k) - starts[sorted_e]
    pos = jnp.zeros((T * k,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)                             # overflow slot

    src = jnp.repeat(x2d, k, axis=0)                             # (T*k, d)
    buf = jnp.zeros((E, cap + 1, d), x.dtype)
    buf = buf.at[flat_e, slot].set(src)                          # dests unique
    ebuf = constrain(buf[:, :cap], "model", None, None)          # expert parallel

    g = jnp.einsum("ecd,edf->ecf", ebuf, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", ebuf, p["w_up"].astype(x.dtype))
    g = constrain(g, "model", None, None)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"].astype(x.dtype))
    y = constrain(y, "model", None, None)

    y_pad = jnp.concatenate([y, jnp.zeros((E, 1, d), y.dtype)], axis=1)
    back = y_pad[flat_e, slot]                                   # (T*k, d)
    back = constrain(back, "batch", None)
    w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(x.dtype)
    out = jnp.sum((back * w[:, None]).reshape(T, k, d), axis=1)
    if mo.num_shared_experts:
        out = out + apply_mlp(p["shared"], x2d)
    return out.reshape(B, S, d), moe_aux_loss(probs, idx, mo)


def apply_moe_dropless(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the layer, dropping no row.

    Every token is routed over all E experts; the (token, slot) pairs
    whose expert this device holds are sorted by expert and run through
    the held experts' SwiGLUs as grouped matrix products
    (kernels/gmm), weighted by their gates and added back to their tokens,
    plus the shared experts once. What the experts held elsewhere would add
    is left out. Returns (out, [aux loss, rows the held experts computed,
    rows of the busiest held expert])."""
    from repro.kernels.gmm.ops import grouped_matmul

    mo = cfg.moe
    B, S, d = x.shape
    T, k, held, f = B * S, mo.top_k, mo.num_held, mo.d_ff_expert
    x2d = x.reshape(T, d)
    # fp32 logits: on the TPU a default-precision f32 product is one bf16
    # pass, which moves the top-k selection of near-tied experts
    gates, idx, probs = _router_probs(p, x2d, mo,
                                      precision=lax.Precision.HIGHEST)
    local = idx.reshape(-1) - mo.first_held                      # (T*k,)
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)          # group `held`: other chips
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    # a token routes to at most min(k, held) held experts: that many rows
    # per token hold every held pair, whatever the routing
    order = jnp.argsort(group, stable=True)[:T * min(k, held)]
    tok = order // k
    w_in = jnp.concatenate([p["w_gate"], p["w_up"]], axis=-1).astype(x.dtype)
    gu = grouped_matmul(x2d[tok], w_in, sizes)                   # (rows, 2f)
    h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    y = grouped_matmul(h, p["w_down"].astype(x.dtype), sizes)    # (rows, d)
    w = jnp.where(mine[order], gates.reshape(-1)[order], 0.0)
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(
        y.astype(jnp.float32) * w[:, None]).astype(x.dtype)
    if mo.num_shared_experts:
        out = out + apply_mlp(p["shared"], x2d)
    stats = jnp.stack([moe_aux_loss(probs, idx, mo),
                       jnp.sum(sizes).astype(jnp.float32),
                       jnp.max(sizes).astype(jnp.float32)])
    return out.reshape(B, S, d), stats


def apply_moe(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (out, [aux loss, rows the held experts computed, rows of the
    busiest one]); the capacity paths count no rows (0, 0)."""
    impl = cfg.moe.impl
    if impl == "dropless":
        return apply_moe_dropless(p, x, cfg)
    if impl == "dense":
        out, aux = apply_moe_dense(p, x, cfg)
    elif impl == "ep":
        from repro.models.moe_ep import apply_moe_ep
        out, aux = apply_moe_ep(p, x, cfg)
    else:
        out, aux = apply_moe_gspmd(p, x, cfg)
    return out, jnp.stack([aux, jnp.zeros(()), jnp.zeros(())])


# --------------------------------------------------------------------------
# RWKV6 (Finch): time-mix with data-dependent decay + channel mix
# --------------------------------------------------------------------------

def init_rwkv6(key, cfg: ModelConfig, dtype) -> Params:
    d = cfg.d_model
    H, hd = cfg.num_heads, cfg.ssm.head_dim
    inner = H * hd
    lora = max(32, d // 16)
    ks = jax.random.split(key, 12)
    return {
        # data-dependent token-shift lerp (5 targets: r,k,v,w,g)
        "mix_base": (jax.random.uniform(ks[0], (5, d), jnp.float32) * 0.5).astype(dtype),
        "mix_lora_a": _dense_init(ks[1], (d, 5, lora // 2), d, dtype),
        "mix_lora_b": _dense_init(ks[2], (5, lora // 2, d), lora, dtype),
        "w_r": _dense_init(ks[3], (d, H, hd), d, dtype),
        "w_k": _dense_init(ks[4], (d, H, hd), d, dtype),
        "w_v": _dense_init(ks[5], (d, H, hd), d, dtype),
        "w_g": _dense_init(ks[6], (d, inner), d, dtype),
        "w_o": _dense_init(ks[7], (H, hd, d), inner, dtype),
        # decay: w_t = exp(-exp(decay_base + lora(x)))
        "decay_base": (jax.random.normal(ks[8], (H, hd), jnp.float32) * 0.3 - 1.0).astype(jnp.float32),
        "decay_lora_a": _dense_init(ks[9], (d, lora), d, dtype),
        "decay_lora_b": _dense_init(ks[10], (lora, H, hd), lora, dtype),
        "bonus": (jax.random.normal(ks[11], (H, hd), jnp.float32) * 0.3).astype(jnp.float32),
        "ln_out": init_rmsnorm(inner, dtype),
    }


def _rwkv6_rkvwg(p: Params, x: jnp.ndarray, x_prev: jnp.ndarray, cfg: ModelConfig):
    """Token-shift data-dependent mixing -> (r, k, v, w(decay, fp32), g)."""
    H, hd = cfg.num_heads, cfg.ssm.head_dim
    shifted = x_prev
    # ddlerp: mix_i = x + (shifted - x) * (base_i + lora_i(x))
    lora_in = jnp.einsum("...d,dml->...ml", x, p["mix_lora_a"].astype(x.dtype))
    lora = jnp.einsum("...ml,mld->...md", jnp.tanh(lora_in), p["mix_lora_b"].astype(x.dtype))
    mixes = x[..., None, :] + (shifted - x)[..., None, :] * (
        p["mix_base"].astype(x.dtype) + lora
    )                                                            # (..., 5, d)
    mixes = constrain(mixes, *(["batch"] + [None] * (mixes.ndim - 2) + ["model"]))
    xr, xk, xv, xw, xg = [mixes[..., i, :] for i in range(5)]
    r = jnp.einsum("...d,dhk->...hk", xr, p["w_r"].astype(x.dtype))
    k = jnp.einsum("...d,dhk->...hk", xk, p["w_k"].astype(x.dtype))
    v = jnp.einsum("...d,dhk->...hk", xv, p["w_v"].astype(x.dtype))
    dl = jnp.einsum("...d,dl->...l", xw, p["decay_lora_a"].astype(x.dtype))
    dw = jnp.einsum("...l,lhk->...hk", jnp.tanh(dl), p["decay_lora_b"].astype(x.dtype))
    # Clip so per-step log-decay >= -e^1.6 ~= -4.95: keeps the chunked
    # factored form (k * exp(-cumdecay)) inside fp32 range for chunk<=16
    # while per-step retention down to e^-4.95 ~= 0.007 covers the practical
    # RWKV6 decay regime (see kernels/rwkv6/ref.py stability note).
    log_w = -jnp.exp(jnp.clip(p["decay_base"] + dw.astype(jnp.float32), -8.0, 1.6))
    g = jax.nn.silu(jnp.einsum("...d,di->...i", xg, p["w_g"].astype(x.dtype)))
    return r, k, v, log_w, g


def rwkv6_timemix(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                  use_pallas: bool = False, return_state: bool = False):
    """Full-sequence RWKV6 time mix. x: (B, S, d). With return_state, also
    returns the final recurrent wkv state (B, H, K, V) for prefill."""
    B, S, d = x.shape
    H, hd = cfg.num_heads, cfg.ssm.head_dim
    x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    r, k, v, log_w, g = _rwkv6_rkvwg(p, x, x_prev, cfg)
    from repro.kernels.rwkv6 import ref as rwkv_ref
    if use_pallas and not return_state:
        from repro.kernels.rwkv6 import ops as rwkv_ops
        o = rwkv_ops.wkv6(r, k, v, log_w, p["bonus"], chunk=cfg.ssm.chunk)
        state = None
    else:
        res = rwkv_ref.wkv6_chunked(r, k, v, log_w, p["bonus"],
                                    chunk=cfg.ssm.chunk,
                                    return_state=return_state,
                                    shard=cfg.ssm.shard)
        o, state = res if return_state else (res, None)
    o = o.reshape(B, S, H * hd).astype(x.dtype)
    o = apply_rmsnorm(p["ln_out"], o, cfg.norm_eps) * g
    out = jnp.einsum("bshk,hkd->bsd", o.reshape(B, S, H, hd), p["w_o"].astype(x.dtype))
    if return_state:
        return out, state
    return out


def init_rwkv6_channelmix(key, cfg: ModelConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "mix_k": (jax.random.uniform(ks[0], (d,), jnp.float32) * 0.5).astype(dtype),
        "w_k": _dense_init(ks[0], (d, f), d, dtype),
        "w_v": _dense_init(ks[1], (f, d), f, dtype),
        "w_r": _dense_init(ks[2], (d, d), d, dtype),
    }


def rwkv6_channelmix(p: Params, x: jnp.ndarray, x_prev: jnp.ndarray) -> jnp.ndarray:
    xk = x + (x_prev - x) * p["mix_k"].astype(x.dtype)
    k = jnp.einsum("...d,df->...f", xk, p["w_k"].astype(x.dtype))
    k = jnp.square(jax.nn.relu(k))
    kv = jnp.einsum("...f,fd->...d", k, p["w_v"].astype(x.dtype))
    r = jax.nn.sigmoid(jnp.einsum("...d,de->...e", xk, p["w_r"].astype(x.dtype)))
    return r * kv


def rwkv6_decode_step(p_tm: Params, p_cm: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                      state: jnp.ndarray, x_prev_att: jnp.ndarray,
                      x_prev_ffn: jnp.ndarray, norm_att: Params, norm_ffn: Params):
    """Single-token RWKV6 block step. x: (B, 1, d). state: (B, H, hd, hd)."""
    B, _, d = x.shape
    H, hd = cfg.num_heads, cfg.ssm.head_dim
    xa = apply_rmsnorm(norm_att, x, cfg.norm_eps)[:, 0]          # (B, d)
    r, k, v, log_w, g = _rwkv6_rkvwg(p_tm, xa, x_prev_att, cfg)
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    u = p_tm["bonus"]
    # o = r · (S + u ⊙ k vᵀ); S' = diag(w) S + k vᵀ
    kv = jnp.einsum("bhk,bhv->bhkv", kf, vf)
    o = jnp.einsum("bhk,bhkv->bhv", rf, state + u[None, :, :, None] * kv)
    new_state = jnp.exp(log_w)[..., None] * state + kv
    o = o.reshape(B, H * hd).astype(x.dtype)
    o = apply_rmsnorm(p_tm["ln_out"], o, cfg.norm_eps) * g
    att_out = jnp.einsum("bhk,hkd->bd", o.reshape(B, H, hd), p_tm["w_o"].astype(x.dtype))
    h = x[:, 0] + att_out
    xf = apply_rmsnorm(norm_ffn, h[:, None], cfg.norm_eps)[:, 0]
    ffn_out = rwkv6_channelmix(p_cm, xf, x_prev_ffn)
    # fp32 token-shift states promote the residual — cast back so the layer
    # scan carry keeps the compute dtype
    out = (h + ffn_out).astype(x.dtype)[:, None]
    return out, new_state, xa.astype(jnp.float32), xf.astype(jnp.float32)


# --------------------------------------------------------------------------
# Mamba2 (SSD)
# --------------------------------------------------------------------------

def init_mamba2(key, cfg: ModelConfig, dtype) -> Params:
    s = cfg.ssm
    d = cfg.d_model
    inner = s.expand * d
    H = inner // s.head_dim
    N = s.state_dim
    conv_ch = inner + 2 * N
    ks = jax.random.split(key, 6)
    return {
        "w_in": _dense_init(ks[0], (d, 2 * inner + 2 * N + H), d, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_dim, conv_ch), jnp.float32) * 0.2).astype(dtype),
        "conv_b": _zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": _ones((H,), jnp.float32),
        "dt_bias": (jax.random.uniform(ks[2], (H,), jnp.float32) * 2.0 - 4.0).astype(jnp.float32),
        "gate_norm": init_rmsnorm(inner, dtype),
        "w_out": _dense_init(ks[3], (inner, d), inner, dtype),
    }


def mamba2_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                   return_state: bool = False):
    """Full-sequence Mamba2 (chunked SSD). x: (B, S, d). With return_state,
    also returns (conv_window (B, K-1, C), ssm_state (B, H, N, P))."""
    s = cfg.ssm
    B, S, d = x.shape
    inner = s.expand * d
    H = inner // s.head_dim
    N = s.state_dim
    proj = jnp.einsum("bsd,de->bse", x, p["w_in"].astype(x.dtype))
    proj = constrain(proj, "batch", None, "model")
    z, xin, Bc, Cc, dt = jnp.split(proj, [inner, 2 * inner, 2 * inner + N, 2 * inner + 2 * N], axis=-1)
    conv_raw = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_raw = constrain(conv_raw, "batch", None, "model")
    conv_in = _causal_conv1d(conv_raw, p["conv_w"].astype(x.dtype), p["conv_b"].astype(x.dtype))
    conv_in = jax.nn.silu(conv_in)
    xin, Bc, Cc = jnp.split(conv_in, [inner, inner + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"])                                      # (H,)
    xh = xin.reshape(B, S, H, s.head_dim)
    y, ssm_state = ssd_chunked(xh, dt, A, Bc, Cc, chunk=s.chunk,
                               return_state=True)                 # (B,S,H,hd)
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, inner).astype(x.dtype)
    y = apply_rmsnorm(p["gate_norm"], y, cfg.norm_eps) * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"].astype(x.dtype))
    if return_state:
        K = s.conv_dim
        pad = jnp.pad(conv_raw, ((0, 0), (K - 1, 0), (0, 0)))
        conv_window = pad[:, -(K - 1):].astype(jnp.float32)
        return out, (conv_window, ssm_state)
    return out


def _causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv. x: (B, S, C); w: (K, C)."""
    K = w.shape[0]
    xpad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(K):                                            # K is tiny (4)
        out = out + xpad[:, i : i + x.shape[1]] * w[i]
    return out + b


def linear_recurrence_pscan(a, b, extra_dims: int = 1):
    """Inclusive prefix states of s_i = a_i ⊙ s_{i-1} + b_i along axis 1 via
    associative scan (log-depth, fully materialized — TPU-parallel and
    honestly counted by HLO cost analysis, unlike a while-loop scan).

    a: (G, n, K); b: (G, n, K, *extra). Returns inclusive states like b."""
    def comb(l, r):
        al, bl = l
        ar, br = r
        arx = ar.reshape(ar.shape + (1,) * extra_dims)
        return al * ar, bl * arx + br

    _, incl = lax.associative_scan(comb, (a, b), axis=1)
    return incl


def _prev_states(a, b, extra_dims: int = 1):
    """(exclusive-prefix states, final state) for the recurrence above."""
    incl = linear_recurrence_pscan(a, b, extra_dims)
    prev = jnp.concatenate(
        [jnp.zeros_like(incl[:, :1]), incl[:, :-1]], axis=1)
    return prev, incl[:, -1]


def ssd_chunked(xh, dt, A, Bc, Cc, *, chunk: int, return_state: bool = False):
    """Chunked state-space-dual scan (Mamba2).

    xh: (B,S,H,P); dt: (B,S,H) fp32; A: (H,) fp32; Bc/Cc: (B,S,N).
    Returns fp32 (B,S,H,P) (+ final state (B,H,N,P) if return_state).
    Scalar-per-head decay -> (L,L) pairwise matrices.
    """
    B, S0, H, P = xh.shape
    N = Bc.shape[-1]
    L = min(chunk, S0)
    pad = (-S0) % L
    if pad:
        # dt = 0 -> unit decay and zero input contribution at padded steps
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
    S = S0 + pad
    nc = S // L
    xb = constrain(xh.reshape(B, nc, L, H, P).astype(jnp.float32),
                   "batch", None, None, "model", None)
    dtb = constrain(dt.reshape(B, nc, L, H), "batch", None, None, "model")
    Bb = Bc.reshape(B, nc, L, N).astype(jnp.float32)
    Cb = Cc.reshape(B, nc, L, N).astype(jnp.float32)

    da = dtb * A[None, None, None, :]                             # (B,nc,L,H) log-decay per step
    cum = jnp.cumsum(da, axis=2)                                  # inclusive
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]          # (B,nc,Lq,Lk,H)
    Lq = jnp.arange(L)
    causal = (Lq[:, None] >= Lq[None, :])[None, None, :, :, None]
    # mask in log space BEFORE exp: the upper triangle has positive log-decay
    # sums that would overflow fp32.
    seg = jnp.exp(jnp.where(causal, diff, -1e30))

    # intra-chunk: y[t] = sum_{i<=t} C_t·B_i seg[t,i] dt_i x_i
    cb = jnp.einsum("bclN,bcmN->bclm", Cb, Bb)                    # (B,nc,Lq,Lk)
    scores = cb[..., None] * seg                                  # (B,nc,Lq,Lk,H)
    y_intra = jnp.einsum("bclmh,bcmh,bcmhp->bclhp", scores, dtb, xb)

    # chunk-final states: S_c = sum_i exp(cum_L - cum_i) dt_i B_i x_i^T
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)               # (B,nc,L,H)
    state_c = jnp.einsum("bclh,bclh,bclN,bclhp->bchNp",
                         decay_to_end, dtb, Bb, xb)               # per-chunk contribution

    # inter-chunk recurrence over chunk index (associative scan, log depth)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                       # (B,nc,H)
    prev_states, final_state = _prev_states(chunk_decay, state_c, extra_dims=2)

    # inter-chunk output: y[t] += C_t · (decay_from_start[t] * prev_state)
    decay_from_start = jnp.exp(cum)                               # (B,nc,L,H)
    y_inter = jnp.einsum("bclN,bclh,bchNp->bclhp", Cb, decay_from_start, prev_states)
    y = (y_intra + y_inter).reshape(B, S, H, P)[:, :S0]
    if return_state:
        return y, final_state
    return y


def init_mamba2_cache(cfg: ModelConfig, batch: int, num_layers: int) -> Params:
    s = cfg.ssm
    inner = s.expand * cfg.d_model
    H = inner // s.head_dim
    N = s.state_dim
    conv_ch = inner + 2 * N
    return {
        "conv": jnp.zeros((num_layers, batch, s.conv_dim - 1, conv_ch), jnp.float32),
        "ssm": jnp.zeros((num_layers, batch, H, N, s.head_dim), jnp.float32),
    }


def mamba2_decode_step(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                       conv_state: jnp.ndarray, ssm_state: jnp.ndarray):
    """Single-token Mamba2 step. x: (B,1,d); conv_state: (B,K-1,C);
    ssm_state: (B,H,N,P)."""
    s = cfg.ssm
    B, _, d = x.shape
    inner = s.expand * d
    H = inner // s.head_dim
    N = s.state_dim
    proj = jnp.einsum("bsd,de->bse", x, p["w_in"].astype(x.dtype))[:, 0]
    z, xin, Bc, Cc, dt = jnp.split(proj, [inner, 2 * inner, 2 * inner + N, 2 * inner + 2 * N], axis=-1)
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)             # (B, C)
    window = jnp.concatenate([conv_state, conv_in[:, None].astype(jnp.float32)], axis=1)  # (B,K,C)
    w = p["conv_w"].astype(jnp.float32)
    conv_out = jnp.einsum("bkc,kc->bc", window, w) + p["conv_b"].astype(jnp.float32)
    conv_out = jax.nn.silu(conv_out).astype(x.dtype)
    new_conv_state = window[:, 1:]
    xin, Bc, Cc = jnp.split(conv_out, [inner, inner + N], axis=-1)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dtf * A[None, :])                             # (B,H)
    xhead = xin.reshape(B, H, s.head_dim).astype(jnp.float32)
    dBx = jnp.einsum("bh,bN,bhp->bhNp", dtf, Bc.astype(jnp.float32), xhead)
    new_ssm = ssm_state * decay[..., None, None] + dBx
    y = jnp.einsum("bN,bhNp->bhp", Cc.astype(jnp.float32), new_ssm)
    y = y + p["D"][None, :, None] * xhead
    y = y.reshape(B, inner).astype(x.dtype)
    y = apply_rmsnorm(p["gate_norm"], y[:, None], cfg.norm_eps)[:, 0] * jax.nn.silu(z)
    out = jnp.einsum("bi,id->bd", y, p["w_out"].astype(x.dtype))
    return out[:, None], new_conv_state, new_ssm
