"""Backbone LM: scan-over-layers transformer covering all assigned families.

Families and their block stacks:
  dense / audio / vlm : uniform [attn + SwiGLU] stack (GQA, sliding window,
                        softcap, qk-norm per config)
  moe                 : [attn + MoE] stack; deepseek additionally has
                        `first_k_dense` leading dense layers, MLA attention,
                        and an MTP head
  ssm (rwkv6)         : [time-mix + channel-mix] stack
  hybrid (zamba2)     : rounds of `hybrid_period` Mamba2 blocks followed by
                        ONE weight-shared attention+MLP block, plus trailing
                        Mamba2 blocks

All stacks are jax.lax.scan over stacked parameters (keeps HLO size and
compile time flat in depth — essential for the 61-layer deepseek dry-run),
with optional jax.checkpoint (remat) on the block body.

Modality frontends (audio/vlm) are prefix stubs: precomputed embeddings
(B, P, d_model) are layer-normed and prepended to the token embeddings; the
loss masks prefix positions out.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.shardingx.constrain import constrain

Params = Dict[str, Any]


# ===========================================================================
# init
# ===========================================================================

def _init_dense_block(key, cfg: ModelConfig, dtype, *, moe_layer: bool) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"ln_attn": L.init_rmsnorm(cfg.d_model, dtype),
                 "ln_mlp": L.init_rmsnorm(cfg.d_model, dtype)}
    if cfg.mla is not None:
        p["attn"] = L.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    if moe_layer:
        p["moe"] = L.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    if cfg.post_block_norm:
        p["ln_post_attn"] = L.init_rmsnorm(cfg.d_model, dtype)
        p["ln_post_mlp"] = L.init_rmsnorm(cfg.d_model, dtype)
    return p


def _init_rwkv_block(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "ln_att": L.init_rmsnorm(cfg.d_model, dtype),
        "ln_ffn": L.init_rmsnorm(cfg.d_model, dtype),
        "tm": L.init_rwkv6(ks[0], cfg, dtype),
        "cm": L.init_rwkv6_channelmix(ks[1], cfg, dtype),
    }


def _init_mamba_block(key, cfg: ModelConfig, dtype) -> Params:
    return {
        "ln": L.init_rmsnorm(cfg.d_model, dtype),
        "mamba": L.init_mamba2(key, cfg, dtype),
    }


def _stacked(init_fn, key, n: int):
    keys = jax.random.split(key, max(n, 1))
    return jax.vmap(init_fn)(keys) if n > 0 else None


def init_params(cfg: ModelConfig, key, param_dtype=jnp.float32) -> Params:
    dtype = jnp.dtype(param_dtype)
    k_embed, k_stack, k_extra, k_head, k_mtp = jax.random.split(key, 5)
    params: Params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model), jnp.float32)
                  * 0.02).astype(dtype),
        "ln_final": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L._dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                          cfg.d_model, dtype)
    if cfg.prefix_frontend:
        params["ln_prefix"] = L.init_rmsnorm(cfg.d_model, dtype)

    fam = cfg.family
    if fam in ("dense", "audio", "vlm"):
        params["layers"] = _stacked(
            lambda k: _init_dense_block(k, cfg, dtype, moe_layer=False),
            k_stack, cfg.num_layers)
    elif fam == "moe":
        n_moe = cfg.num_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            params["dense_layers"] = _stacked(
                lambda k: _init_dense_block(k, cfg, dtype, moe_layer=False),
                k_extra, cfg.first_k_dense)
        params["layers"] = _stacked(
            lambda k: _init_dense_block(k, cfg, dtype, moe_layer=True),
            k_stack, n_moe)
        if cfg.mtp_depth:
            km1, km2 = jax.random.split(k_mtp)
            params["mtp"] = {
                "proj": L._dense_init(km1, (2 * cfg.d_model, cfg.d_model),
                                      2 * cfg.d_model, dtype),
                "ln_h": L.init_rmsnorm(cfg.d_model, dtype),
                "ln_e": L.init_rmsnorm(cfg.d_model, dtype),
                "block": _init_dense_block(km2, cfg, dtype, moe_layer=False),
            }
    elif fam == "ssm":
        params["layers"] = _stacked(lambda k: _init_rwkv_block(k, cfg, dtype),
                                    k_stack, cfg.num_layers)
    elif fam == "hybrid":
        rounds, trailing = _hybrid_split(cfg)
        params["layers"] = _stacked(lambda k: _init_mamba_block(k, cfg, dtype),
                                    k_stack, rounds * cfg.hybrid_period)
        if trailing:
            params["tail_layers"] = _stacked(
                lambda k: _init_mamba_block(k, cfg, dtype), k_extra, trailing)
        ks1, ks2 = jax.random.split(k_head if cfg.tie_embeddings else k_mtp)
        params["shared_block"] = _init_dense_block(ks1, cfg, dtype, moe_layer=False)
    else:
        raise ValueError(f"unknown family {fam}")
    return params


def _hybrid_split(cfg: ModelConfig) -> Tuple[int, int]:
    rounds = cfg.num_layers // cfg.hybrid_period
    trailing = cfg.num_layers - rounds * cfg.hybrid_period
    return rounds, trailing


# ===========================================================================
# forward (train / prefill)
# ===========================================================================

def _local_flags(cfg: ModelConfig, n: int) -> jnp.ndarray:
    if cfg.attn_variant == "sliding":
        return jnp.ones((n,), bool)
    if cfg.attn_variant == "alternating":
        return (jnp.arange(n) % 2) == 0
    return jnp.zeros((n,), bool)


def _dense_block_apply(lp: Params, x, cfg: ModelConfig, *, positions,
                       is_local, use_pallas: bool, moe_layer: bool):
    x = constrain(x, "batch", None, None)
    h = L.apply_rmsnorm(lp["ln_attn"], x, cfg.norm_eps)
    if cfg.mla is not None:
        attn = L.mla_attention(lp["attn"], h, cfg, positions=positions,
                               is_local=is_local)
    else:
        attn = L.multi_head_attention(lp["attn"], h, cfg, positions=positions,
                                      is_local=is_local, use_pallas=use_pallas)
    if cfg.post_block_norm:
        attn = L.apply_rmsnorm(lp["ln_post_attn"], attn, cfg.norm_eps)
    x = x + attn
    h = L.apply_rmsnorm(lp["ln_mlp"], x, cfg.norm_eps)
    aux = jnp.zeros((3,), jnp.float32)      # L.apply_moe's [aux, rows, max]
    if moe_layer:
        out, aux = L.apply_moe(lp["moe"], h, cfg)
    else:
        out = L.apply_mlp(lp["mlp"], h)
    if cfg.post_block_norm:
        out = L.apply_rmsnorm(lp["ln_post_mlp"], out, cfg.norm_eps)
    return x + out, aux


def _rwkv_block_apply(lp: Params, x, cfg: ModelConfig, *, use_pallas: bool):
    x = constrain(x, "batch", None, None)
    h = L.apply_rmsnorm(lp["ln_att"], x, cfg.norm_eps)
    x = x + _timemix_full(lp["tm"], h, cfg, use_pallas)
    h = L.apply_rmsnorm(lp["ln_ffn"], x, cfg.norm_eps)
    h_prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return x + L.rwkv6_channelmix(lp["cm"], h, h_prev)


def _timemix_full(tm, h, cfg, use_pallas):
    return L.rwkv6_timemix(tm, h, cfg, use_pallas=use_pallas)


def _mamba_block_apply(lp: Params, x, cfg: ModelConfig):
    x = constrain(x, "batch", None, None)
    h = L.apply_rmsnorm(lp["ln"], x, cfg.norm_eps)
    return x + L.mamba2_forward(lp["mamba"], h, cfg)


def _scan_stack(body, x, stacked, flags=None, remat: bool = True):
    """Scan `body(x, layer_params, flag) -> (x, aux)` over stacked params —
    statically unrolled under layers.unrolled() (dry-run accounting)."""
    def f(carry, xs):
        lp, flag = xs
        out, aux = body(carry, lp, flag)
        return out, aux

    if remat:
        f = jax.checkpoint(f, prevent_cse=L.unroll_mode())
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if flags is None:
        flags = jnp.zeros((n,), bool)
    x, auxs = L.maybe_scan(f, x, (stacked, flags))
    return x, jnp.sum(auxs, axis=0)


def embed_inputs(params: Params, tokens, cfg: ModelConfig, *,
                 prefix_embeds=None):
    """-> (x (B, P+S, d), positions (B, P+S), loss_mask (B, P+S))."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * math.sqrt(cfg.d_model)
    loss_mask = jnp.ones((B, S), bool)
    if cfg.prefix_frontend:
        assert prefix_embeds is not None, f"{cfg.name} requires prefix_embeds"
        pe = L.apply_rmsnorm(params["ln_prefix"], prefix_embeds.astype(x.dtype),
                             cfg.norm_eps)
        x = jnp.concatenate([pe, x], axis=1)
        loss_mask = jnp.concatenate(
            [jnp.zeros((B, pe.shape[1]), bool), loss_mask], axis=1)
    T = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return x, positions, loss_mask


def forward(params: Params, tokens, cfg: ModelConfig, *, prefix_embeds=None,
            use_pallas: bool = False, remat: bool = True,
            compute_dtype=jnp.bfloat16, return_logits: bool = True):
    """-> (logits (B, T, V) fp32 | None, hidden (B, T, d), aux): aux holds
    the MoE load-balance loss and, summed over the MoE layers, the rows the
    held experts computed and the rows of each layer's busiest one."""
    x, positions, loss_mask = embed_inputs(params, tokens, cfg,
                                           prefix_embeds=prefix_embeds)
    x = x.astype(compute_dtype)
    fam = cfg.family
    aux_total = jnp.zeros((3,), jnp.float32)

    if fam in ("dense", "audio", "vlm", "moe"):
        if fam == "moe" and cfg.first_k_dense:
            def dense_body(h, lp, flag):
                return _dense_block_apply(lp, h, cfg, positions=positions,
                                          is_local=flag, use_pallas=use_pallas,
                                          moe_layer=False)
            x, _ = _scan_stack(dense_body, x, params["dense_layers"],
                               flags=_local_flags(cfg, cfg.first_k_dense),
                               remat=remat)

        moe_layer = fam == "moe"
        n_main = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]

        def body(h, lp, flag):
            return _dense_block_apply(lp, h, cfg, positions=positions,
                                      is_local=flag, use_pallas=use_pallas,
                                      moe_layer=moe_layer)
        x, aux_total = _scan_stack(body, x, params["layers"],
                                   flags=_local_flags(cfg, n_main), remat=remat)

    elif fam == "ssm":
        def body(h, lp, flag):
            return _rwkv_block_apply(lp, h, cfg, use_pallas=use_pallas), jnp.zeros((), jnp.float32)
        x, _ = _scan_stack(body, x, params["layers"], remat=remat)

    elif fam == "hybrid":
        rounds, trailing = _hybrid_split(cfg)
        per = cfg.hybrid_period
        stacked = jax.tree.map(
            lambda a: a.reshape((rounds, per) + a.shape[1:]), params["layers"])

        def round_body(h, round_params, flag):
            def inner(hh, lp, _):
                return _mamba_block_apply(lp, hh, cfg), jnp.zeros((), jnp.float32)
            h, _ = _scan_stack(inner, h, round_params, remat=False)
            h, _ = _dense_block_apply(params["shared_block"], h, cfg,
                                      positions=positions, is_local=flag,
                                      use_pallas=use_pallas, moe_layer=False)
            return h, jnp.zeros((), jnp.float32)

        shared_local = _local_flags(cfg, rounds)
        x, _ = _scan_stack(round_body, x, stacked, flags=shared_local, remat=remat)
        if trailing:
            def tail(h, lp, flag):
                return _mamba_block_apply(lp, h, cfg), jnp.zeros((), jnp.float32)
            x, _ = _scan_stack(tail, x, params["tail_layers"], remat=remat)
    else:
        raise ValueError(fam)

    hidden = L.apply_rmsnorm(params["ln_final"], x, cfg.norm_eps)
    logits = _lm_logits(params, hidden, cfg) if return_logits else None
    return logits, hidden, {"moe_aux": aux_total[0],
                            "expert_rows": aux_total[1],
                            "expert_rows_max": aux_total[2],
                            "loss_mask": loss_mask}


def _lm_logits(params: Params, hidden, cfg: ModelConfig):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", hidden, head.astype(hidden.dtype))
    logits = logits.astype(jnp.float32)
    if cfg.final_logit_softcap:
        logits = jnp.tanh(logits / cfg.final_logit_softcap) * cfg.final_logit_softcap
    return logits


# ===========================================================================
# loss
# ===========================================================================

def softmax_xent(logits, labels, mask):
    """Mean next-token cross-entropy over masked positions. logits fp32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    # feddcl-lint: disable=R006  mask is a {0,1} token count: real mass is >= 1 so the 1.0 clamp never deflates, it only turns the all-masked batch into 0/1 = 0 instead of 0/0
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


XENT_CHUNK = 512            # sequence-block size for the chunked CE head


def chunked_xent(params, hidden, labels, mask, cfg: ModelConfig,
                 chunk: int = XENT_CHUNK):
    """CE over the vocab head computed in sequence blocks: the (B, S, V)
    logit tensor (4 GiB/device at 256k vocab × 1M tokens) never materializes
    — peak head temp is (B, chunk, V)."""
    B, S, d = hidden.shape
    if S % chunk or S <= chunk:
        logits = _lm_logits(params, hidden, cfg)
        return softmax_xent(logits, labels, mask)
    n = S // chunk
    hs = hidden.reshape(B, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)
    ms = mask.reshape(B, n, chunk).swapaxes(0, 1)

    def body(carry, xs):
        tot, cnt = carry
        h, l, m = xs
        logits = constrain(_lm_logits(params, h, cfg), "batch", None, "model")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum((logz - gold) * m)
        cnt = cnt + jnp.sum(m)
        return (tot, cnt), None

    (tot, cnt), _ = L.maybe_scan(body, (jnp.zeros(()), jnp.zeros(())), (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
            use_pallas: bool = False, remat: bool = True,
            compute_dtype=jnp.bfloat16, mtp_coef: float = 0.3,
            aux_coef: float = 0.01):
    """batch: tokens (B,S), labels (B,S) (next token, -1 = ignore),
    optional prefix_embeds (B,P,d)."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    _, hidden, aux = forward(
        params, tokens, cfg, prefix_embeds=batch.get("prefix_embeds"),
        use_pallas=use_pallas, remat=remat, compute_dtype=compute_dtype,
        return_logits=False)
    # align: prefix positions carry no labels
    P = hidden.shape[1] - tokens.shape[1]
    tok_hidden = hidden[:, P:]
    mask = (labels >= 0) & aux["loss_mask"][:, P:]
    loss = chunked_xent(params, tok_hidden, jnp.maximum(labels, 0),
                        mask.astype(jnp.float32), cfg)
    metrics = {"ce": loss}
    if cfg.moe is not None:
        loss = loss + aux_coef * aux["moe_aux"]
        for k in ("moe_aux", "expert_rows", "expert_rows_max"):
            metrics[k] = aux[k]
    if cfg.mtp_depth and "mtp" in params:
        mtp_loss = _mtp_loss(params, hidden[:, P:], tokens, labels, cfg,
                             compute_dtype)
        loss = loss + mtp_coef * mtp_loss
        metrics["mtp"] = mtp_loss
    metrics["loss"] = loss
    return loss, metrics


def _mtp_loss(params, hidden, tokens, labels, cfg: ModelConfig, compute_dtype):
    """DeepSeek-V3 multi-token prediction (depth 1): at position t, combine
    the main hidden state with the embedding of token t+1 and predict t+2."""
    mp = params["mtp"]
    B, S, d = hidden.shape
    h = L.apply_rmsnorm(mp["ln_h"], hidden[:, :-1], cfg.norm_eps)
    e = jnp.take(params["embed"], tokens[:, 1:], axis=0).astype(h.dtype)
    e = L.apply_rmsnorm(mp["ln_e"], e, cfg.norm_eps)
    x = jnp.einsum("bsd,dk->bsk", jnp.concatenate([h, e], -1),
                   mp["proj"].astype(h.dtype))
    positions = jnp.broadcast_to(jnp.arange(S - 1, dtype=jnp.int32), (B, S - 1))
    x, _ = _dense_block_apply(mp["block"], x, cfg, positions=positions,
                              is_local=False, use_pallas=False, moe_layer=False)
    x = L.apply_rmsnorm(params["ln_final"], x, cfg.norm_eps)
    # labels for t+2 = labels shifted left by one; last position invalid
    mtp_labels = labels[:, 1:]                              # (B, S-1)
    mask = (mtp_labels >= 0).astype(jnp.float32)
    # trim to a chunk multiple so the CE head stays chunked at scale
    Sm = x.shape[1]
    keep = (Sm // XENT_CHUNK) * XENT_CHUNK if Sm > XENT_CHUNK else Sm
    return chunked_xent(params, x[:, :keep], jnp.maximum(mtp_labels[:, :keep], 0),
                        mask[:, :keep], cfg)


# ===========================================================================
# prefill: full-sequence forward that also fills the decode cache
# ===========================================================================

def _fill_cache(entries, positions, cache_len: int):
    """entries: (L, B, S, ...) per-position cache writes; keep the last
    min(S, cache_len) positions at ring slots pos % cache_len."""
    Ln, B, S = entries.shape[:3]
    W = min(S, cache_len)
    ent = entries[:, :, S - W:]
    pos = positions[S - W:]                                 # (W,)
    slots = pos % cache_len
    cache = jnp.zeros((Ln, B, cache_len) + entries.shape[3:], entries.dtype)
    cache = cache.at[:, :, slots].set(ent)
    pos_arr = jnp.full((Ln, B, cache_len), -1, jnp.int32)
    pos_arr = pos_arr.at[:, :, slots].set(jnp.broadcast_to(pos, (Ln, B, W)))
    return cache, pos_arr


def prefill(params: Params, tokens, cfg: ModelConfig, *, cache_len: int,
            prefix_embeds=None, use_pallas: bool = False, remat: bool = True,
            compute_dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16):
    """Process a full prompt, returning (last-position logits (B, 1, V),
    decode state matching init_decode_state, next position (B,))."""
    x, positions, _ = embed_inputs(params, tokens, cfg,
                                   prefix_embeds=prefix_embeds)
    x = x.astype(compute_dtype)
    B, T = positions.shape
    pos1d = jnp.arange(T, dtype=jnp.int32)
    fam = cfg.family
    state: Params = {}

    def attn_stack(x, stacked, n, moe_layer):
        flags = _local_flags(cfg, n)

        def body(h, lp, flag):
            hn = L.apply_rmsnorm(lp["ln_attn"], h, cfg.norm_eps)
            if cfg.mla is not None:
                attn, (ckv, krope) = L.mla_attention(
                    lp["attn"], hn, cfg, positions=positions, is_local=flag,
                    return_kv=True)
                entry = (ckv.astype(cache_dtype), krope.astype(cache_dtype))
            else:
                attn, (k, v) = L.multi_head_attention(
                    lp["attn"], hn, cfg, positions=positions, is_local=flag,
                    use_pallas=use_pallas, return_kv=True)
                entry = (k.astype(cache_dtype), v.astype(cache_dtype))
            if cfg.post_block_norm:
                attn = L.apply_rmsnorm(lp["ln_post_attn"], attn, cfg.norm_eps)
            h = h + attn
            hn = L.apply_rmsnorm(lp["ln_mlp"], h, cfg.norm_eps)
            if moe_layer:
                out, _ = L.apply_moe(lp["moe"], hn, cfg)
            else:
                out = L.apply_mlp(lp["mlp"], hn)
            if cfg.post_block_norm:
                out = L.apply_rmsnorm(lp["ln_post_mlp"], out, cfg.norm_eps)
            return h + out, entry

        def f(carry, xs):
            lp, flag = xs
            return body(carry, lp, flag)
        if remat:
            f = jax.checkpoint(f, prevent_cse=L.unroll_mode())
        return L.maybe_scan(f, x, (stacked, flags))

    if fam in ("dense", "audio", "vlm", "moe"):
        if cfg.first_k_dense:
            x, ent = attn_stack(x, params["dense_layers"], cfg.first_k_dense, False)
            state["dense_cache"] = _entries_to_cache(ent, pos1d, cache_len, cfg)
        n_main = (cfg.num_layers - cfg.first_k_dense) if fam == "moe" else cfg.num_layers
        x, ent = attn_stack(x, params["layers"], n_main, fam == "moe")
        state["cache"] = _entries_to_cache(ent, pos1d, cache_len, cfg)

    elif fam == "ssm":
        def body(h, lp, flag):
            hn = L.apply_rmsnorm(lp["ln_att"], h, cfg.norm_eps)
            att, wkv = L.rwkv6_timemix(lp["tm"], hn, cfg, return_state=True)
            h = h + att
            hf = L.apply_rmsnorm(lp["ln_ffn"], h, cfg.norm_eps)
            hf_prev = jnp.pad(hf, ((0, 0), (1, 0), (0, 0)))[:, :-1]
            h = h + L.rwkv6_channelmix(lp["cm"], hf, hf_prev)
            return h, (wkv, hn[:, -1].astype(jnp.float32), hf[:, -1].astype(jnp.float32))

        def f(carry, xs):
            lp, flag = xs
            return body(carry, lp, flag)
        if remat:
            f = jax.checkpoint(f, prevent_cse=L.unroll_mode())
        x, (wkv, xpa, xpf) = L.maybe_scan(
            f, x, (params["layers"], jnp.zeros((cfg.num_layers,), bool)))
        state.update({"wkv": wkv, "x_prev_att": xpa, "x_prev_ffn": xpf})

    elif fam == "hybrid":
        rounds, trailing = _hybrid_split(cfg)
        per = cfg.hybrid_period
        stacked = jax.tree.map(
            lambda a: a.reshape((rounds, per) + a.shape[1:]), params["layers"])
        is_local = jnp.asarray(cfg.attn_variant == "sliding")

        def round_body(h, rp, flag):
            def inner(hh, lp, _):
                hn = L.apply_rmsnorm(lp["ln"], hh, cfg.norm_eps)
                out, st = L.mamba2_forward(lp["mamba"], hn, cfg, return_state=True)
                return hh + out, st
            h, mstates = L.maybe_scan(lambda c, xs: inner(c, xs, None), h, rp)
            hn = L.apply_rmsnorm(params["shared_block"]["ln_attn"], h, cfg.norm_eps)
            attn, (k, v) = L.multi_head_attention(
                params["shared_block"]["attn"], hn, cfg, positions=positions,
                is_local=is_local, use_pallas=use_pallas, return_kv=True)
            h = h + attn
            hn = L.apply_rmsnorm(params["shared_block"]["ln_mlp"], h, cfg.norm_eps)
            h = h + L.apply_mlp(params["shared_block"]["mlp"], hn)
            return h, (mstates, k.astype(cache_dtype), v.astype(cache_dtype))

        def f(carry, xs):
            rp, flag = xs
            return round_body(carry, rp, flag)
        if remat:
            f = jax.checkpoint(f, prevent_cse=L.unroll_mode())
        x, (mstates, ks, vs) = L.maybe_scan(
            f, x, (stacked, jnp.zeros((rounds,), bool)))
        conv = mstates[0].reshape((rounds * per,) + mstates[0].shape[2:])
        ssm = mstates[1].reshape((rounds * per,) + mstates[1].shape[2:])
        state["mamba"] = {"conv": conv, "ssm": ssm}
        kc, pos_arr = _fill_cache(ks, pos1d, cache_len)
        vc, _ = _fill_cache(vs, pos1d, cache_len)
        state["shared_cache"] = {"k": kc, "v": vc, "pos": pos_arr}
        if trailing:
            def tail(hh, xs):
                lp = xs
                hn = L.apply_rmsnorm(lp["ln"], hh, cfg.norm_eps)
                out, st = L.mamba2_forward(lp["mamba"], hn, cfg, return_state=True)
                return hh + out, st
            x, tstates = L.maybe_scan(tail, x, params["tail_layers"])
            state["mamba_tail"] = {"conv": tstates[0], "ssm": tstates[1]}

    hidden = L.apply_rmsnorm(params["ln_final"], x[:, -1:], cfg.norm_eps)
    logits = _lm_logits(params, hidden, cfg)
    next_pos = jnp.full((B,), T, jnp.int32)
    return logits, state, next_pos


def _entries_to_cache(ent, pos1d, cache_len: int, cfg: ModelConfig):
    if cfg.mla is not None:
        ckv, krope = ent
        c1, pos_arr = _fill_cache(ckv, pos1d, cache_len)
        c2, _ = _fill_cache(krope, pos1d, cache_len)
        return {"ckv": c1, "krope": c2, "pos": pos_arr}
    k, v = ent
    kc, pos_arr = _fill_cache(k, pos1d, cache_len)
    vc, _ = _fill_cache(v, pos1d, cache_len)
    return {"k": kc, "v": vc, "pos": pos_arr}


# ===========================================================================
# decode (single token, cached)
# ===========================================================================

def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int,
                      dtype=jnp.bfloat16) -> Params:
    """Cache pytree for serve_step. cache_len should be min(seq_len, window)
    for pure sliding-window configs."""
    fam = cfg.family
    state: Params = {}
    if fam in ("dense", "audio", "vlm", "moe"):
        n_main = cfg.num_layers - cfg.first_k_dense if fam == "moe" else cfg.num_layers
        if cfg.mla is not None:
            if cfg.first_k_dense:
                state["dense_cache"] = L.init_mla_cache(cfg, batch, cache_len,
                                                        cfg.first_k_dense, dtype)
            state["cache"] = L.init_mla_cache(cfg, batch, cache_len, n_main, dtype)
        else:
            if cfg.first_k_dense:
                state["dense_cache"] = L.init_kv_cache(cfg, batch, cache_len,
                                                       cfg.first_k_dense, dtype)
            state["cache"] = L.init_kv_cache(cfg, batch, cache_len, n_main, dtype)
    elif fam == "ssm":
        H, hd = cfg.num_heads, cfg.ssm.head_dim
        state["wkv"] = jnp.zeros((cfg.num_layers, batch, H, hd, hd), jnp.float32)
        state["x_prev_att"] = jnp.zeros((cfg.num_layers, batch, cfg.d_model), jnp.float32)
        state["x_prev_ffn"] = jnp.zeros((cfg.num_layers, batch, cfg.d_model), jnp.float32)
    elif fam == "hybrid":
        rounds, trailing = _hybrid_split(cfg)
        state["mamba"] = L.init_mamba2_cache(cfg, batch, rounds * cfg.hybrid_period)
        if trailing:
            state["mamba_tail"] = L.init_mamba2_cache(cfg, batch, trailing)
        state["shared_cache"] = L.init_kv_cache(cfg, batch, cache_len, rounds, dtype)
    return state


def decode_step(params: Params, state: Params, tokens, cur_pos,
                cfg: ModelConfig, *, compute_dtype=jnp.bfloat16):
    """One decode step. tokens: (B, 1) int32; cur_pos: (B,) absolute position.
    Returns (logits (B, 1, V) fp32, new_state)."""
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens[:, 0], axis=0)[:, None]
    if cfg.scale_embeddings:
        x = x * math.sqrt(cfg.d_model)
    x = x.astype(compute_dtype)
    fam = cfg.family
    new_state = dict(state)

    if fam in ("dense", "audio", "vlm", "moe"):
        if cfg.first_k_dense:
            x, new_state["dense_cache"] = _decode_attn_stack(
                params["dense_layers"], state["dense_cache"], x, cur_pos, cfg,
                moe_layer=False, n=cfg.first_k_dense)
        n_main = (cfg.num_layers - cfg.first_k_dense) if fam == "moe" else cfg.num_layers
        x, new_state["cache"] = _decode_attn_stack(
            params["layers"], state["cache"], x, cur_pos, cfg,
            moe_layer=(fam == "moe"), n=n_main)

    elif fam == "ssm":
        def body(carry, xs):
            h = carry
            lp, wkv, xpa, xpf = xs
            out, new_wkv, new_xpa, new_xpf = L.rwkv6_decode_step(
                lp["tm"], lp["cm"], h, cfg, state=wkv, x_prev_att=xpa,
                x_prev_ffn=xpf, norm_att=lp["ln_att"], norm_ffn=lp["ln_ffn"])
            return out, (new_wkv, new_xpa, new_xpf)
        x, (wkv, xpa, xpf) = L.maybe_scan(
            body, x, (params["layers"], state["wkv"], state["x_prev_att"],
                      state["x_prev_ffn"]))
        new_state.update({"wkv": wkv, "x_prev_att": xpa, "x_prev_ffn": xpf})

    elif fam == "hybrid":
        rounds, trailing = _hybrid_split(cfg)
        per = cfg.hybrid_period
        reshape = lambda a: a.reshape((rounds, per) + a.shape[1:])
        stacked = jax.tree.map(reshape, params["layers"])
        mcache = {k: reshape(v) for k, v in state["mamba"].items()}
        is_local = cfg.attn_variant == "sliding"

        def round_body(carry, xs):
            h = carry
            rp, conv, ssm, ck, cv, cp = xs

            def inner(hh, ys):
                lp, cv_, ss_ = ys
                hn = L.apply_rmsnorm(lp["ln"], hh, cfg.norm_eps)
                out, nc, ns = L.mamba2_decode_step(lp["mamba"], hn, cfg,
                                                   conv_state=cv_, ssm_state=ss_)
                return hh + out, (nc, ns)
            h, (nconv, nssm) = L.maybe_scan(inner, h, (rp, conv, ssm))
            hn = L.apply_rmsnorm(params["shared_block"]["ln_attn"], h, cfg.norm_eps)
            attn, (nk, nv, npos) = L.decode_attention(
                params["shared_block"]["attn"], hn, cfg, cache_k=ck, cache_v=cv,
                cache_pos=cp, cur_pos=cur_pos, is_local=is_local)
            h = h + attn
            hn = L.apply_rmsnorm(params["shared_block"]["ln_mlp"], h, cfg.norm_eps)
            h = h + L.apply_mlp(params["shared_block"]["mlp"], hn)
            return h, (nconv, nssm, nk, nv, npos)

        x, (nconv, nssm, nk, nv, npos) = L.maybe_scan(
            round_body, x,
            (stacked, mcache["conv"], mcache["ssm"],
             state["shared_cache"]["k"], state["shared_cache"]["v"],
             state["shared_cache"]["pos"]))
        unshape = lambda a: a.reshape((rounds * per,) + a.shape[2:])
        new_state["mamba"] = {"conv": unshape(nconv), "ssm": unshape(nssm)}
        new_state["shared_cache"] = {"k": nk, "v": nv, "pos": npos}
        if trailing:
            def tail(carry, xs):
                h = carry
                lp, conv, ssm = xs
                hn = L.apply_rmsnorm(lp["ln"], h, cfg.norm_eps)
                out, nc, ns = L.mamba2_decode_step(lp["mamba"], hn, cfg,
                                                   conv_state=conv, ssm_state=ssm)
                return h + out, (nc, ns)
            x, (tc, ts) = L.maybe_scan(tail, x, (params["tail_layers"],
                                             state["mamba_tail"]["conv"],
                                             state["mamba_tail"]["ssm"]))
            new_state["mamba_tail"] = {"conv": tc, "ssm": ts}

    hidden = L.apply_rmsnorm(params["ln_final"], x, cfg.norm_eps)
    logits = _lm_logits(params, hidden, cfg)
    return logits, new_state


def _decode_attn_stack(stacked, cache, x, cur_pos, cfg: ModelConfig, *,
                       moe_layer: bool, n: int):
    flags = _local_flags(cfg, n)
    use_mla = cfg.mla is not None

    def body(carry, xs):
        h = carry
        if use_mla:
            lp, ckv, krope, cpos, flag = xs
        else:
            lp, ck, cv, cpos, flag = xs
        hn = L.apply_rmsnorm(lp["ln_attn"], h, cfg.norm_eps)
        if use_mla:
            attn, (nckv, nkrope, npos) = L.mla_decode(
                lp["attn"], hn, cfg, cache_ckv=ckv, cache_krope=krope,
                cache_pos=cpos, cur_pos=cur_pos, is_local=flag)
        else:
            attn, (nk, nv, npos) = L.decode_attention(
                lp["attn"], hn, cfg, cache_k=ck, cache_v=cv, cache_pos=cpos,
                cur_pos=cur_pos, is_local=flag)
        if cfg.post_block_norm:
            attn = L.apply_rmsnorm(lp["ln_post_attn"], attn, cfg.norm_eps)
        h = h + attn
        hn = L.apply_rmsnorm(lp["ln_mlp"], h, cfg.norm_eps)
        if moe_layer:
            out, _ = L.apply_moe(lp["moe"], hn, cfg)
        else:
            out = L.apply_mlp(lp["mlp"], hn)
        if cfg.post_block_norm:
            out = L.apply_rmsnorm(lp["ln_post_mlp"], out, cfg.norm_eps)
        if use_mla:
            return h + out, (nckv, nkrope, npos)
        return h + out, (nk, nv, npos)

    if use_mla:
        xs = (stacked, cache["ckv"], cache["krope"], cache["pos"], flags)
        x, (a, b, c) = L.maybe_scan(body, x, xs)
        return x, {"ckv": a, "krope": b, "pos": c}
    xs = (stacked, cache["k"], cache["v"], cache["pos"], flags)
    x, (a, b, c) = L.maybe_scan(body, x, xs)
    return x, {"k": a, "v": b, "pos": c}


# ===========================================================================
# analytic parameter counts (exact — from eval_shape of init)
# ===========================================================================

@functools.lru_cache(maxsize=None)
def _param_shapes(cfg: ModelConfig):
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    return shapes


def count_params_analytic(cfg: ModelConfig, active_only: bool = False,
                          include_embed: bool = True) -> int:
    shapes = _param_shapes(cfg)
    total = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    if not include_embed:
        total -= cfg.vocab_size * cfg.d_model
        if not cfg.tie_embeddings:
            total -= cfg.vocab_size * cfg.d_model
    if active_only and cfg.moe is not None:
        # of the held experts, top_k × held / E take a token on average
        mo = cfg.moe
        n_moe = cfg.num_layers - cfg.first_k_dense
        inactive = (n_moe * 3 * cfg.d_model * mo.d_ff_expert
                    * (mo.num_held - mo.top_k * mo.num_held / mo.num_experts))
        total -= int(inactive)
    return total
