"""Plain float32 reference of the Moonlight-16B-A3B block for the CPU tests
(the DeepSeek-V3 block: MLA, without q-LoRA when ``q_lora_rank`` is 0; a
sigmoid router over all experts with a bias that steers the selection
only; shared experts): forward pass, loss, gradients and AdamW steps on
the program's parameter tree, in straightforward ``jax.numpy`` under the
highest matmul precision. No kernels, no chunking, no cache.

The expert share is applied by masking the full routing: the experts
outside [first_held, first_held + held) add nothing, and the held ones
take every (token, slot) routed to them.

Departures from the published model, each as the program defines it:

- AdamW (decoupled decay on every leaf, global-norm clipping) in place of
  Moonlight's Muon;
- the Switch-style load-balance loss E · Σ_e f_e · P_e per MoE layer with
  coefficient 0.01, since the config does not give ``aux_loss_alpha``;
- ``noaux_tc``'s router bias held fixed, since its update rule is not in
  the config;
- RoPE rotating each head's first and second halves, a fixed permutation
  of the published interleaved layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        p["scale"]


def rope(x, theta):
    """x (B, S, heads, r)."""
    S, half = x.shape[1], x.shape[-1] // 2
    freq = jnp.exp(-jnp.arange(half) * (np.log(theta) / half))
    ang = jnp.arange(S)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mla(p, h, cfg):
    m, eps = cfg.mla, cfg.norm_eps
    if m.q_lora_rank:
        q = jnp.einsum("bsr,rhk->bshk", rms(h @ p["w_dq"], p["q_norm"], eps),
                       p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, p["w_q"])
    q = jnp.concatenate([q[..., :m.qk_nope_head_dim],
                         rope(q[..., m.qk_nope_head_dim:], cfg.rope_theta)],
                        -1)
    kv = h @ p["w_dkv"]
    c = rms(kv[..., :m.kv_lora_rank], p["kv_norm"], eps)
    k_rope = rope(kv[..., None, m.kv_lora_rank:], cfg.rope_theta)
    k_nope = jnp.einsum("bsr,rhk->bshk", c, p["w_uk"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, k_nope.shape[:3] + (m.qk_rope_head_dim,))], -1)
    v = jnp.einsum("bsr,rhk->bshk", c, p["w_uv"])
    S = h.shape[1]
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k) / np.sqrt(q.shape[-1])
    logits = jnp.where(jnp.tril(jnp.ones((S, S), bool)), logits, -jnp.inf)
    ctx = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(logits, -1), v)
    return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def route(p, x2d, mo):
    """-> (gates over all E experts (T, E), zero where not chosen; the
    chosen experts (T, k); the scores (T, E))."""
    scores = jax.nn.sigmoid(x2d @ p["router"])
    _, idx = jax.lax.top_k(scores + p["router_bias"], mo.top_k)
    chosen = jax.nn.one_hot(idx, mo.num_experts).sum(1)
    g = scores * chosen
    return g / (g.sum(-1, keepdims=True) + 1e-9) * mo.routed_scaling, idx, \
        scores


def moe(p, h, mo):
    """The held experts' part of the layer plus the shared experts, and
    the layer's load-balance loss."""
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    gates, idx, scores = route(p, x, mo)
    held = gates[:, mo.first_held:mo.first_held + mo.num_held]
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(
        jnp.einsum("td,edf->tef", x, p["w_gate"])) * jnp.einsum(
        "td,edf->tef", x, p["w_up"]), p["w_down"])
    out = jnp.einsum("ted,te->td", y, held)
    if mo.num_shared_experts:
        out = out + swiglu(p["shared"], x)
    f = jax.nn.one_hot(idx, mo.num_experts).sum((0, 1)) / (
        x.shape[0] * mo.top_k)
    aux = mo.num_experts * jnp.sum(f * scores.mean(0))
    return out.reshape(B, S, d), aux


def loss(params, tokens, labels, cfg, aux_coef=0.01):
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        aux = 0.0
        for name, is_moe in (("dense_layers", False), ("layers", True)):
            stack = params[name]
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                p = jax.tree.map(lambda w, i=i: w[i], stack)
                x = x + mla(p["attn"], rms(x, p["ln_attn"], cfg.norm_eps),
                            cfg)
                h = rms(x, p["ln_mlp"], cfg.norm_eps)
                if is_moe:
                    out, a = moe(p["moe"], h, cfg.moe)
                    aux = aux + a
                else:
                    out = swiglu(p["mlp"], h)
                x = x + out
        z = rms(x, params["ln_final"], cfg.norm_eps) @ params["lm_head"]
        gold = jnp.take_along_axis(z, labels[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(z, -1) - gold) + aux_coef * aux


def adamw_round(params, batches, cfg, *, lr, b1=0.9, b2=0.95, eps=1e-8,
                wd=0.1, clip=1.0):
    """H AdamW steps from fresh state over batches (H, B, S): -> (losses,
    gradient norms before clipping, final params)."""
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, norms = [], []
    for t in range(1, batches["tokens"].shape[0] + 1):
        val, g = jax.value_and_grad(loss)(
            params, batches["tokens"][t - 1], batches["labels"][t - 1], cfg)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (norm + 1e-9)),
                         g)
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        params = jax.tree.map(
            lambda p, a, b: p - lr * ((a / (1 - b1 ** t)) / (
                jnp.sqrt(b / (1 - b2 ** t)) + eps) + wd * p), params, m, v)
        losses.append(float(val))
        norms.append(float(norm))
    return np.asarray(losses), np.asarray(norms), params
