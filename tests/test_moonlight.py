"""Moonlight-16B-A3B's block against its plain float32 reference
(tests/_mla_moe_ref.py) at a small size on seeded random weights: MLA
without q-LoRA, the expert layer that holds a share of the experts and
routes over all of them without dropping a row, and one FedDCL round
through the path ``launch/train.py`` runs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _mla_moe_ref as ref
from repro.configs import REDUCED
from repro.configs.base import FederatedConfig, InputShape, TrainConfig
from repro.core.federated import silo_replicate
from repro.kernels.flash_attention import ops as fa_ops
from repro.launch.train import compile_round, run_rounds
from repro.models import backbone as bb
from repro.models import layers as L

CFG = REDUCED["moonlight-16b-a3b"]


def _moe_cfg(num_experts=16, held=0, first=0, top_k=6):
    moe = dataclasses.replace(CFG.moe, num_experts=num_experts, top_k=top_k,
                              held_experts=held, first_held=first)
    return CFG.with_overrides(moe=moe)


def _moe_params(cfg, key, hot=None):
    p = L.init_moe(key, cfg, jnp.float32)
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 1),
                                    p["router_bias"].shape)
    if hot is not None:           # one expert takes nearly every token
        bias = bias.at[hot].set(10.0)
    return dict(p, router_bias=bias)


def _held(p, first, n):
    return dict(p, **{k: p[k][first:first + n]
                      for k in ("w_gate", "w_up", "w_down")})


def test_q_lora_rank_0_projects_queries_directly():
    assert CFG.mla.q_lora_rank == 0
    assert REDUCED["deepseek-v3-671b"].mla.q_lora_rank == 64
    p = L.init_mla(jax.random.PRNGKey(0), CFG, jnp.float32)
    assert "w_q" in p and "w_dq" not in p and "w_uq" not in p
    shapes = jax.eval_shape(lambda: bb.init_params(CFG, jax.random.PRNGKey(0)))
    assert bb.count_params_analytic(CFG) == sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "deepseek-v3-671b"])
def test_mla_attention_matches_reference(arch):
    cfg = REDUCED[arch]
    key = jax.random.PRNGKey(1)
    p = L.init_mla(key, cfg, jnp.float32)
    h = jax.random.normal(jax.random.fold_in(key, 2), (2, 16, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    with jax.default_matmul_precision("highest"):
        got = L.mla_attention(p, h, cfg, positions=pos)
        want = ref.mla(p, h, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "deepseek-v3-671b"])
def test_mla_attention_on_the_causal_kernel_matches_reference(arch,
                                                              monkeypatch):
    """The kernel path forced on the CPU (splash in interpret mode) over
    two 1024-blocks."""
    monkeypatch.setattr(L, "causal_flash_applies", lambda *a, **k: True)
    monkeypatch.setattr(fa_ops, "_resolve", lambda backend: "interpret")
    cfg = REDUCED[arch]
    key = jax.random.PRNGKey(1)
    p = L.init_mla(key, cfg, jnp.float32)
    h = jax.random.normal(jax.random.fold_in(key, 2), (1, 2048, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(2048), (1, 2048))
    with jax.default_matmul_precision("highest"):
        got = L.mla_attention(p, h, cfg, positions=pos)
        want = ref.mla(p, h, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("backend,seq,flag,variant,softcap,kernel", [
    ("cpu", 4096, None, "full", 0.0, False),
    ("tpu", 4608, None, "full", 0.0, False),      # 4.5 blocks of 1024
    ("tpu", 4096, True, "full", 0.0, False),      # a sliding-window layer
    ("tpu", 4096, None, "alternating", 0.0, False),
    ("tpu", 4096, None, "full", 50.0, False),
    ("tpu", 4096, None, "full", 0.0, True),
    ("tpu", 4096, False, "full", 0.0, True),
], ids=["not-tpu", "seq-not-block-multiple", "sliding-window",
        "traced-window-flag", "softcap", "tpu", "tpu-flag-false"])
def test_full_sequence_attention_routing(backend, seq, flag, variant,
                                         softcap, kernel, monkeypatch):
    """`mla_attention` takes the causal kernel only on a TPU, at a length
    its blocks tile, with no window and no softcap; anything else keeps
    `sdpa`. A `None` flag is the layer scan's traced one."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if softcap:                  # mla_attention itself takes no softcap
        assert L.causal_flash_applies(seq, is_local=False,
                                      attn_variant=variant, softcap=0.0)
        assert not L.causal_flash_applies(seq, is_local=False,
                                          attn_variant=variant,
                                          softcap=softcap)
        return
    cfg = CFG.with_overrides(attn_variant=variant)
    taken = []

    def route(name):
        def core(q, k, v, **_):
            taken.append(name)
            return jnp.zeros(q.shape[:3] + v.shape[3:], q.dtype)
        return core

    monkeypatch.setattr(L, "sdpa", route("sdpa"))
    monkeypatch.setattr(fa_ops, "causal_attention", route("kernel"))
    p = L.init_mla(jax.random.PRNGKey(0), cfg, jnp.float32)
    h = jax.ShapeDtypeStruct((1, seq, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(seq), (1, seq))
    if flag is None:
        jax.eval_shape(lambda h, f: L.mla_attention(
            p, h, cfg, positions=pos, is_local=f), h, jnp.zeros((), bool))
    else:
        jax.eval_shape(lambda h: L.mla_attention(
            p, h, cfg, positions=pos, is_local=flag), h)
    assert taken == ["kernel" if kernel else "sdpa"]


def test_prefill_then_mla_decode_matches_the_full_forward():
    key = jax.random.PRNGKey(2)
    params = bb.init_params(CFG, key)
    tokens = jax.random.randint(key, (2, 12), 0, CFG.vocab_size)
    with jax.default_matmul_precision("highest"):
        full, _, _ = bb.forward(params, tokens, CFG,
                                compute_dtype=jnp.float32)
        logits, state, pos = bb.prefill(params, tokens[:, :8], CFG,
                                        cache_len=12,
                                        compute_dtype=jnp.float32,
                                        cache_dtype=jnp.float32)
        got = [logits[:, 0]]
        for t in range(8, 11):
            step, state = bb.decode_step(params, state, tokens[:, t:t + 1],
                                         pos, CFG, compute_dtype=jnp.float32)
            got.append(step[:, 0])
            pos = pos + 1
    np.testing.assert_allclose(np.stack(got, 1), full[:, 7:11],
                               rtol=2e-4, atol=2e-4)


def test_shares_add_up_to_the_uncut_layer():
    """E = 16 held as 4 shares of 4: the parts every share computes add up
    to the uncut reference layer, the shared experts counted once."""
    cfg = _moe_cfg()
    p = _moe_params(cfg, jax.random.PRNGKey(3))
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        parts = [L.apply_moe(_held(p, 4 * i, 4), h,
                             _moe_cfg(held=4, first=4 * i))[0]
                 for i in range(4)]
        shared = L.apply_mlp(p["shared"], h)
        whole, _ = ref.moe(p, h, cfg.moe)
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-4)


def test_dropless_equals_dense_when_one_expert_takes_most_tokens():
    cfg = _moe_cfg()
    p = _moe_params(cfg, jax.random.PRNGKey(5), hot=3)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, stats = L.apply_moe_dropless(p, h, cfg)
        dense, aux = L.apply_moe_dense(p, h, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(stats[0]), float(aux), rtol=1e-6)
    assert float(stats[1]) == 2 * 32 * 6          # every pair held here
    assert float(stats[2]) == 2 * 32               # expert 3 took them all


@pytest.mark.parametrize("first", [0, 4, 12])
def test_held_experts_compute_every_row_routed_to_them(first):
    """Under a bias that sends nearly every token to one held expert, the
    rows computed are exactly the (token, slot) pairs the full routing
    sends to the held experts: none dropped."""
    cfg = _moe_cfg(held=4, first=first)
    p = _moe_params(_moe_cfg(), jax.random.PRNGKey(7), hot=first + 1)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, stats = L.apply_moe(_held(p, first, 4), h, cfg)
        want, _ = ref.moe(_held(p, first, 4), h, cfg.moe)
        _, idx, _ = ref.route(p, h.reshape(-1, cfg.d_model), cfg.moe)
    routed = np.asarray((idx >= first) & (idx < first + 4))
    assert float(stats[1]) == routed.sum()
    counts = np.bincount(np.asarray(idx)[routed] - first, minlength=4)
    assert float(stats[2]) == counts.max() == 64
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


def test_one_feddcl_round_matches_the_reference():
    """One FedDCL round of the reduced config on one silo's share (2 of 4
    experts held), through compile_round and run_rounds: each local step's
    loss and gradient norm, and each leaf's change over the round."""
    moe = dataclasses.replace(CFG.moe, held_experts=2, first_held=1)
    cfg = CFG.with_overrides(moe=moe)
    H, B, S, lr = 3, 2, 16, 1e-3
    tc = TrainConfig(model=cfg, shape=InputShape("t", S, B, "train"),
                     federated=FederatedConfig(num_silos=1, local_steps=H),
                     learning_rate=lr, weight_decay=0.1, warmup_steps=0,
                     total_steps=2 ** 30, grad_clip=1.0,
                     compute_dtype="float32", remat=True)
    key = jax.random.PRNGKey(9)
    params = bb.init_params(cfg, key)
    params["layers"]["moe"]["router_bias"] = 0.05 * jax.random.normal(
        key, params["layers"]["moe"]["router_bias"].shape)
    ids = np.asarray(jax.random.randint(key, (H, 1, B, S + 1), 0,
                                        cfg.vocab_size), np.int32)
    batches = {"tokens": ids[..., :-1], "labels": ids[..., 1:]}
    step, opt = compile_round(cfg, tc)
    sp = silo_replicate(params, 1)
    with jax.default_matmul_precision("highest"):
        sp, _, (got,) = run_rounds(step, sp, jax.vmap(opt.init)(sp),
                                   lambda r: batches, 1)
        losses, norms, final = ref.adamw_round(
            params, {k: v[:, 0] for k, v in batches.items()}, cfg, lr=lr)
    np.testing.assert_allclose(got["loss"][:, 0], losses, rtol=1e-5)
    np.testing.assert_allclose(got["grad_norm"][:, 0], norms, rtol=1e-4)
    assert got["expert_rows"].shape == (H, 1)
    for a, b, p0 in zip(jax.tree.leaves(sp), jax.tree.leaves(final),
                        jax.tree.leaves(params)):
        np.testing.assert_allclose(np.linalg.norm(a[0] - p0),
                                   np.linalg.norm(b - p0), rtol=1e-3,
                                   atol=1e-7)
