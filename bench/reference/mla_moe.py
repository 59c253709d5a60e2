"""Plain reference of one chip's share of Moonlight-16B-A3B in training
(the DeepSeek-V3 block, arXiv:2412.19437; the configuration's file names
the published config): its forward pass, loss, gradients and AdamW steps,
in float32 at the highest matmul precision.

The block, as the configuration states it:

- RMSNorm (eps from the file) before attention and before the MLP;
- MLA without q-LoRA: q = h W_q (H heads of nope + rope), the shared
  latent c = RMSNorm(h W_dkv[:, :rank]) and one rope key h W_dkv[:, rank:],
  k_nope = c W_uk, v = c W_uv; RoPE (theta from the file) on the rope
  parts, rotating each head's first and second halves ("half" layout, a
  fixed permutation of the published interleaved one, which a projection
  learns alike); causal softmax over (nope ‖ rope) scaled by its root;
- the first layers a SwiGLU of the dense width, the rest MoE: a sigmoid
  router over all E experts, top-k on scores plus a bias that steers the
  selection only, gates = scores of the chosen over their sum times the
  routed scaling factor; the experts this chip holds (the file's share)
  each a SwiGLU, weighted by their gate where chosen (the full routing,
  masked to the held experts: what the others would add is left out);
  the shared experts once, as one SwiGLU of their summed width;
- the head over the vocabulary slice, mean next-token cross-entropy.

Departures from the published training, each the program's own:

- AdamW (the file's beta, eps, decay, clip) in place of Moonlight's Muon,
  with decoupled decay on every leaf;
- a Switch-style load-balance loss, E · Σ_e f_e · P_e over the step's
  tokens per MoE layer, with coefficient 0.01 (the config gives no
  ``aux_loss_alpha``);
- the router bias held fixed within a window (``noaux_tc``'s update rule
  is not in the config), drawn from the seed.

Everything is computed in blocks so that a step at the cell's sizes fits
one chip beside nothing else: each layer is recomputed in the backward
pass, attention per sequence and query block, the held experts and the
head per sequence. ``lowp=True`` rounds every matrix product's operands
to float8_e4m3fn (the precision below the program's bfloat16): the
control. ``fault`` plants one of the faults the comparison must catch.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FAULTS = ("held_only", "no_shared", "no_bias", "capacity", "half_batch")
QBLOCK = 1024
CAPACITY = 1.25


@dataclass(frozen=True)
class Shape:
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    dense_ff: int
    expert_ff: int
    shared_ff: int
    n_dense: int
    n_moe: int
    experts: int
    held: int
    first: int
    top_k: int
    scaling: float
    vocab: int
    eps: float
    theta: float
    aux_coef: float


def shape_of(cfg: dict) -> Shape:
    n_dense = cfg["first_k_dense_replace"]
    return Shape(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
        dense_ff=cfg["intermediate_size"],
        expert_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        n_dense=n_dense, n_moe=cfg["num_hidden_layers"] - n_dense,
        experts=cfg["deployment"]["router_experts"],
        held=cfg["n_routed_experts"],
        first=cfg["deployment"]["first_held_expert"],
        top_k=cfg["num_experts_per_tok"],
        scaling=cfg["routed_scaling_factor"], vocab=cfg["vocab_size"],
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        aux_coef=cfg["train"]["aux_coef"])


# -- weights -------------------------------------------------------------------

def _mat(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def _attn(key, s: Shape, n: int):
    k = jax.random.split(key, 5)
    qk = s.nope + s.rope
    return {"w_q": _mat(k[0], (n, s.d, s.heads, qk), s.d),
            "w_dkv": _mat(k[1], (n, s.d, s.rank + s.rope), s.d),
            "kv_norm": {"scale": jnp.ones((n, s.rank), jnp.float32)},
            "w_uk": _mat(k[2], (n, s.rank, s.heads, s.nope), s.rank),
            "w_uv": _mat(k[3], (n, s.rank, s.heads, s.v), s.rank),
            "wo": _mat(k[4], (n, s.heads, s.v, s.d), s.heads * s.v)}


def _swiglu(key, d, f, lead=()):
    k = jax.random.split(key, 3)
    return {"w_gate": _mat(k[0], lead + (d, f), d),
            "w_up": _mat(k[1], lead + (d, f), d),
            "w_down": _mat(k[2], lead + (f, d), f)}


def _norms(s: Shape, n: int):
    one = {"scale": jnp.ones((n, s.d), jnp.float32)}
    return {"ln_attn": one, "ln_mlp": dict(one)}


@partial(jax.jit, static_argnames=("s", "bias_std"))
def init_weights(key, s: Shape, bias_std: float):
    """The benchmark's weights, drawn from `key` on the device: every
    matrix N(0, 1/fan_in), the embedding N(0, 0.02²), norms 1, the router
    bias N(0, bias_std²). The tree is the program's parameter layout."""
    k = jax.random.split(key, 8)
    moe = _swiglu(k[3], s.d, s.expert_ff, (s.n_moe, s.held))
    moe["router"] = _mat(k[4], (s.n_moe, s.d, s.experts), s.d)
    moe["router_bias"] = bias_std * jax.random.normal(
        k[5], (s.n_moe, s.experts), jnp.float32)
    moe["shared"] = _swiglu(k[6], s.d, s.shared_ff, (s.n_moe,))
    return {
        "embed": 0.02 * jax.random.normal(k[0], (s.vocab, s.d), jnp.float32),
        "lm_head": _mat(k[1], (s.d, s.vocab), s.d),
        "ln_final": {"scale": jnp.ones((s.d,), jnp.float32)},
        "dense_layers": dict(_norms(s, s.n_dense),
                             attn=_attn(k[2], s, s.n_dense),
                             mlp=_swiglu(k[7], s.d, s.dense_ff, (s.n_dense,))),
        "layers": dict(_norms(s, s.n_moe), attn=_attn(jax.random.fold_in(
            k[2], 1), s, s.n_moe), moe=moe),
    }


# -- the mathematics -------------------------------------------------------------

def _round(x, lowp: bool):
    """x, or x rounded to float8_e4m3fn in the forward pass (the control);
    the gradient passes through unrounded."""
    if not lowp:
        return x
    return x + lax.stop_gradient(
        x.astype(jnp.float8_e4m3fn).astype(jnp.float32) - x)


def mm(spec: str, a, b, lowp: bool):
    return jnp.einsum(spec, _round(a, lowp), _round(b, lowp),
                      precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (..., S, heads, r): rotate the first and second halves by the
    position's angles."""
    S, half = x.shape[-3], x.shape[-1] // 2
    freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (
        np.log(theta) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq     # (S, half)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(p, h, lowp):
    g = mm("td,df->tf", h, p["w_gate"], lowp)
    u = mm("td,df->tf", h, p["w_up"], lowp)
    return mm("tf,fd->td", jax.nn.silu(g) * u, p["w_down"], lowp)


def attention(p, h, s: Shape, lowp):
    """h (B, S, d) -> (B, S, d)."""
    B, S, _ = h.shape
    q = mm("bsd,dhk->bshk", h, p["w_q"], lowp)
    q = jnp.concatenate([q[..., :s.nope], rope(q[..., s.nope:], s.theta)], -1)
    kv = mm("bsd,dr->bsr", h, p["w_dkv"], lowp)
    c = rmsnorm(kv[..., :s.rank], p["kv_norm"]["scale"], s.eps)
    k_rope = rope(kv[..., None, s.rank:], s.theta)               # (B,S,1,r)
    k = jnp.concatenate([mm("bsr,rhk->bshk", c, p["w_uk"], lowp),
                         jnp.broadcast_to(k_rope, (B, S, s.heads, s.rope))],
                        -1)
    v = mm("bsr,rhk->bshk", c, p["w_uv"], lowp)
    qb = min(QBLOCK, S)
    nq = S // qb

    @jax.checkpoint
    def block(xs):
        b, i = xs
        qc = lax.dynamic_slice_in_dim(q[b], i * qb, qb)          # (qb,H,k)
        logits = mm("qhk,shk->hqs", qc, k[b], lowp) / np.sqrt(
            s.nope + s.rope)
        pos = i * qb + jnp.arange(qb)
        logits = jnp.where(jnp.arange(S)[None] <= pos[:, None], logits,
                           -jnp.inf)
        return mm("hqs,shk->qhk", jax.nn.softmax(logits, -1), v[b], lowp)

    bs, iq = jnp.meshgrid(jnp.arange(B), jnp.arange(nq), indexing="ij")
    ctx = lax.map(block, (bs.reshape(-1), iq.reshape(-1)))        # (B*nq,...)
    ctx = ctx.reshape(B, S, s.heads, s.v)
    return mm("bshk,hkd->bsd", ctx, p["wo"], lowp)


def route(p, h2d, s: Shape, lowp, fault):
    """-> (gates over the E experts (T, E), zero where not chosen; scores;
    chosen experts (T, k))."""
    scores = jax.nn.sigmoid(mm("td,de->te", h2d, p["router"], lowp))
    sel = scores if fault == "no_bias" else scores + p["router_bias"]
    if fault == "held_only":
        mine = (jnp.arange(s.experts) >= s.first) & (
            jnp.arange(s.experts) < s.first + s.held)
        sel = jnp.where(mine, sel, -jnp.inf)
    _, idx = lax.top_k(sel, s.top_k)
    chosen = jax.nn.one_hot(idx, s.experts, dtype=jnp.float32).sum(1)
    g = scores * chosen
    return g / (g.sum(-1, keepdims=True) + 1e-9) * s.scaling, scores, idx


def moe(p, h, s: Shape, lowp, fault):
    """h (B, S, d) -> (out (B, S, d), load-balance loss, share of the
    top-k selections the router bias changed)."""
    B, S, d = h.shape
    T = B * S
    h2d = h.reshape(T, d)
    gates, scores, idx = route(p, h2d, s, lowp, fault)
    held = gates[:, s.first:s.first + s.held]                    # (T, held)
    if fault == "capacity":
        cap = int(CAPACITY * T * s.top_k / s.experts)
        took = held > 0
        held = jnp.where(took & (jnp.cumsum(took, 0) <= cap), held, 0.0)

    @jax.checkpoint
    def experts(xs):
        x, w = xs                                                # (S,d),(S,held)
        g = mm("td,edf->tef", x, p["w_gate"], lowp)
        u = mm("td,edf->tef", x, p["w_up"], lowp)
        y = mm("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"], lowp)
        return jnp.einsum("ted,te->td", y, w, precision=HIGHEST)

    out = lax.map(experts, (h, held.reshape(B, S, s.held))).reshape(T, d)
    if fault != "no_shared":
        out = out + swiglu(p["shared"], h2d, lowp)
    f = jax.nn.one_hot(idx, s.experts, dtype=jnp.float32).sum((0, 1)) / (
        T * s.top_k)
    aux = s.experts * jnp.sum(f * scores.mean(0))
    _, plain = lax.top_k(scores, s.top_k)
    kept = (plain[:, :, None] == idx[:, None, :]).any(-1)
    return out.reshape(B, S, d), aux, 1.0 - jnp.mean(kept)


def loss_fn(params, tokens, labels, s: Shape, lowp=False, fault=None):
    """Mean next-token cross-entropy plus aux_coef × the MoE layers'
    load-balance losses, and the mean share of the top-k selections the
    router bias changed. tokens, labels (B, S)."""
    x = params["embed"][tokens]

    def layer(x, p, kind):
        h = rmsnorm(x, p["ln_attn"]["scale"], s.eps)
        x = x + attention(p["attn"], h, s, lowp)
        h = rmsnorm(x, p["ln_mlp"]["scale"], s.eps)
        if kind == "dense":
            B, S, d = h.shape
            return x + swiglu(p["mlp"], h.reshape(B * S, d),
                              lowp).reshape(B, S, d), 0.0, 0.0
        out, aux, changed = moe(p["moe"], h, s, lowp, fault)
        return x + out, aux, changed

    aux = changed = 0.0
    for kind, stack in (("dense", params["dense_layers"]),
                        ("moe", params["layers"])):
        n = jax.tree.leaves(stack)[0].shape[0]
        for i in range(n):
            x, a, c = jax.checkpoint(partial(layer, kind=kind))(
                x, jax.tree.map(lambda w, i=i: w[i], stack))
            aux, changed = aux + a, changed + c / s.n_moe
    h = rmsnorm(x, params["ln_final"]["scale"], s.eps)

    @jax.checkpoint
    def xent(xs):
        hs, ls = xs
        z = mm("sd,dv->sv", hs, params["lm_head"], lowp)
        gold = jnp.take_along_axis(z, ls[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, -1) - gold)

    ce = jnp.sum(lax.map(xent, (h, labels))) / labels.size
    return ce + s.aux_coef * aux, lax.stop_gradient(changed)


@partial(jax.jit, static_argnames=("s", "hp", "lowp", "fault"),
         donate_argnums=(0, 1, 2))
def adamw_step(params, m, v, t, tokens, labels, *, s: Shape, hp: tuple,
               lowp: bool, fault: Optional[str]):
    """One AdamW step on one batch: -> (params, m, v, loss, gradient norm
    before clipping, share of selections the bias changed). hp: (lr, b1,
    b2, eps, weight decay, clip)."""
    lr, b1, b2, eps, wd, clip = hp
    (loss, changed), g = jax.value_and_grad(loss_fn, has_aux=True)(
        params, tokens, labels, s, lowp, fault)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (norm + 1e-9)), g)
    t = t + 1.0
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / (1 - b1 ** t)) / (
            jnp.sqrt(b / (1 - b2 ** t)) + eps) + wd * p), params, m, v)
    return params, m, v, loss, norm, changed


@jax.jit
def change_norms(new, old):
    """Norm of each leaf's change."""
    return jnp.stack([jnp.linalg.norm((a - b).reshape(-1))
                      for a, b in zip(jax.tree.leaves(new),
                                      jax.tree.leaves(old))])


def local_round(init, batches, s: Shape, hp: tuple, lowp: bool = False,
                fault: Optional[str] = None):
    """The H local steps of one silo's round from the weights `init()`
    gives (called twice: the round donates its copy). batches: (H, B, S)
    tokens and labels. -> dict of NumPy: per-step losses and gradient
    norms, each leaf's change norm over the round, and the share of the
    top-k selections the router bias changed in the first step."""
    params = init()
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, norms, changed = [], [], []
    B = batches["tokens"].shape[1]
    keep = B // 2 if fault == "half_batch" else B   # half the batch left out
    for h in range(batches["tokens"].shape[0]):
        params, m, v, loss, norm, c = adamw_step(
            params, m, v, jnp.float32(h),
            jnp.asarray(batches["tokens"][h, :keep]),
            jnp.asarray(batches["labels"][h, :keep]), s=s, hp=hp, lowp=lowp,
            fault=fault)
        losses.append(float(loss))
        norms.append(float(norm))
        changed.append(float(c))
    del m, v
    change = np.asarray(change_norms(params, init()))
    return {"loss": np.asarray(losses), "grad_norm": np.asarray(norms),
            "change": change, "bias_changed": changed[0]}
