"""Per-architecture smoke tests (deliverable f): REDUCED variant of each
family — one forward + one train step on CPU, asserting shapes and no NaNs;
plus prefill/decode consistency against the full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, INPUT_SHAPES, REDUCED
from repro.configs.base import InputShape, TrainConfig
from repro.launch import steps as steps_lib
from repro.models import backbone as bb
from repro.models.modality import synthetic_prefix

ARCH_IDS = sorted(REDUCED)


def _batch(cfg, key, B=2, S=32):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    if cfg.prefix_frontend:
        batch["prefix_embeds"] = synthetic_prefix(key, cfg, B)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = REDUCED[arch]
    key = jax.random.PRNGKey(0)
    params = bb.init_params(cfg, key)
    batch = _batch(cfg, key)
    logits, hidden, aux = bb.forward(params, batch["tokens"], cfg,
                                     prefix_embeds=batch.get("prefix_embeds"),
                                     compute_dtype=jnp.float32)
    T = 32 + (cfg.prefix_len if cfg.prefix_frontend else 0)
    assert logits.shape == (2, T, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_reduces_loss_and_is_finite(arch):
    cfg = REDUCED[arch]
    key = jax.random.PRNGKey(0)
    shape = InputShape("t", seq_len=32, global_batch=2, kind="train")
    tc = TrainConfig(model=cfg, shape=shape, learning_rate=5e-3, remat=False,
                     warmup_steps=1, total_steps=10, param_dtype="float32",
                     compute_dtype="float32")
    step, opt = steps_lib.make_train_step(cfg, tc)
    step = jax.jit(step)
    params = bb.init_params(cfg, key, jnp.float32)
    opt_state = opt.init(params)
    batch = _batch(cfg, key)
    losses = []
    for _ in range(3):
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses     # same batch -> must descend


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    cfg = REDUCED[arch]
    key = jax.random.PRNGKey(0)
    B, S = 2, 24
    params = bb.init_params(cfg, key)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    pe = synthetic_prefix(key, cfg, B) if cfg.prefix_frontend else None
    logits_full, _, _ = bb.forward(params, tokens, cfg, prefix_embeds=pe,
                                   compute_dtype=jnp.float32)
    cache_len = S + (cfg.prefix_len if cfg.prefix_frontend else 0)
    pf_logits, state, next_pos = bb.prefill(
        params, tokens[:, :S - 1], cfg, cache_len=cache_len,
        prefix_embeds=pe, compute_dtype=jnp.float32, cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(pf_logits[:, 0]),
                               np.asarray(logits_full[:, -2]),
                               atol=1e-4, rtol=1e-4)
    dec_logits, _ = bb.decode_step(params, state, tokens[:, S - 1:S],
                                   next_pos, cfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(dec_logits[:, 0]),
                               np.asarray(logits_full[:, -1]),
                               atol=1e-4, rtol=1e-4)


def test_all_archs_and_shapes_registered():
    assert len(ARCHS) == 11
    assert len(INPUT_SHAPES) == 4
    fams = {cfg.family for cfg in ARCHS.values()}
    assert fams == {"dense", "moe", "ssm", "hybrid", "audio", "vlm"}
    for cfg in REDUCED.values():
        assert cfg.num_layers <= 3 and cfg.d_model <= 512
        if cfg.moe:
            assert cfg.moe.num_experts <= 4


def test_sliding_window_variant_long_context():
    """long_500k policy: sliding variant decodes with a ring cache shorter
    than the sequence."""
    cfg = REDUCED["llama3.2-1b"].with_overrides(attn_variant="sliding",
                                                sliding_window=8)
    key = jax.random.PRNGKey(0)
    params = bb.init_params(cfg, key)
    state = bb.init_decode_state(cfg, 1, cache_len=8, dtype=jnp.float32)
    tok = jnp.zeros((1, 1), jnp.int32)
    for pos in range(20):                     # run far past the window
        logits, state = bb.decode_step(params, state, tok,
                                       jnp.asarray([pos]), cfg,
                                       compute_dtype=jnp.float32)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_gemma2_softcap_bounds_logits():
    cfg = REDUCED["gemma2-2b"]
    key = jax.random.PRNGKey(0)
    params = bb.init_params(cfg, key)
    batch = _batch(cfg, key)
    logits, _, _ = bb.forward(params, batch["tokens"], cfg,
                              compute_dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits))) <= cfg.final_logit_softcap + 1e-3
