"""The Pallas kernels compile with the TPU compiler at the widths they run at.

Interpret mode (tests/test_kernels.py) never checks what Mosaic refuses:
block shapes off the (8, 128) tiling, primitives with no TPU lowering,
fast-memory budgets. Each case here compiles one kernel for one chip of a
described v5e:2x2 topology — nothing runs, so no chip is needed — and
asserts the compiled program holds the Mosaic custom call. The topology is
described inside a fixture (never at import), because only one process may
load the TPU library and every pytest worker imports this file.

Widths: the Gram stacks the chip smoke reaches at the paper's MNIST layout
(group stacks d=5 × (c·m̃=200), the central and onboarding stacks at 250,
battery_small's 16), flash attention at llama3.2-1b's heads, wkv6 at
rwkv6-3b's, and, forward and backward under the silo vmap at
moonlight-16b-a3b's widths, the grouped matmul (expert widths, the rows of
4 × 4096 tokens × top-6) and the causal splash kernels (MLA's 16 heads of
qk 192 and v 128 over 4 × 4096 tokens).
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.configs import ARCHS
from repro.kernels.flash_attention.ops import (causal_attention,
                                               flash_attention)
from repro.kernels.gmm.ops import grouped_matmul
from repro.kernels.gram.ops import gram_batched
from repro.kernels.rwkv6.ops import wkv6


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("shape", [
    (5, 2000, 200),      # d=5 group stacks, c·m̃ = 200 (not a multiple of 128)
    (1, 2000, 250),      # central stack, d·m̂ = 250
    (1, 2000, 250),      # onboarding Gram: group 0 grown to 5 users
    (1, 2000, 16),       # battery_small width
], ids=["groups", "central", "onboard", "small"])
def test_gram_compiles_for_v5e(one_chip, shape):
    a = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(
        partial(gram_batched, backend="pallas"), a)


def test_flash_attention_compiles_for_v5e(one_chip):
    cfg = ARCHS["llama3.2-1b"]
    seq = 4096
    q = jax.ShapeDtypeStruct((1, seq, cfg.num_heads, cfg.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, cfg.num_kv_heads, cfg.head_dim),
                              jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(
        partial(flash_attention, backend="pallas"), q, kv, kv)


def test_wkv6_compiles_for_v5e(one_chip):
    cfg = ARCHS["rwkv6-3b"]
    seq, heads, k = 4096, cfg.num_heads, cfg.ssm.head_dim
    x = jax.ShapeDtypeStruct((1, seq, heads, k), jnp.float32,
                             sharding=one_chip)
    u = jax.ShapeDtypeStruct((heads, k), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(
        partial(wkv6, chunk=cfg.ssm.chunk, backend="pallas"), x, x, x, x, u)


def test_grouped_matmul_and_its_vjp_compile_for_v5e(one_chip):
    moe = ARCHS["moonlight-16b-a3b"].moe
    d, f, held = ARCHS["moonlight-16b-a3b"].d_model, moe.d_ff_expert, 8
    rows = 4 * 4096 * moe.top_k
    mm = partial(grouped_matmul, backend="pallas")

    def loss(x, w_in, w_out, sizes):
        gu = mm(x, w_in, sizes)
        h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        return jnp.sum(mm(h, w_out, sizes).astype(jnp.float32))

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1,) + s, dtype, sharding=one_chip)

    text = _compiled_text(jax.vmap(jax.grad(loss, argnums=(0, 1, 2))),
                          shape(rows, d), shape(held, d, 2 * f),
                          shape(held, f, d), shape(held, dtype=jnp.int32))
    assert "tpu_custom_call" in text and "grouped_matmul_t_pallas" in text


def test_causal_attention_and_its_vjp_compile_for_v5e(one_chip):
    cfg = ARCHS["moonlight-16b-a3b"]
    m, heads = cfg.mla, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim

    def loss(q, k, v):
        out = causal_attention(q, k, v, backend="pallas")
        return jnp.sum(out.astype(jnp.float32))

    def shape(width):
        return jax.ShapeDtypeStruct((1, 4, 4096, heads, width), jnp.bfloat16,
                                    sharding=one_chip)

    text = _compiled_text(jax.vmap(jax.grad(loss, argnums=(0, 1, 2))),
                          shape(qk), shape(qk), shape(m.v_head_dim))
    assert "tpu_custom_call" in text
    assert all(f"splash_mha_{k}" in text for k in ("fwd", "dq", "dkv"))
