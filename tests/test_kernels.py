"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.gmm import ops as gmm_ops, ref as gmm_ref
from repro.kernels.gram import ops as gram_ops, ref as gram_ref
from repro.kernels.rwkv6 import ops as rwkv_ops, ref as rwkv_ref
from repro.models import layers as L


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 4, 4, 64, 32),       # MHA
    (2, 8, 2, 128, 64),      # GQA 4:1
    (1, 8, 8, 256, 128),     # long-ish, MXU-aligned head
    (2, 4, 1, 64, 64),       # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, H, KV, S, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out_ref = fa_ops.flash_attention(q, k, v, backend="ref")
    out_pal = fa_ops.flash_attention(q, k, v, backend="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_pal, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (32, 0.0), (0, 50.0),
                                            (48, 30.0)])
def test_flash_attention_variants(window, softcap):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, KV, hd = 2, 128, 8, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    a = fa_ops.flash_attention(q, k, v, window=window, softcap=softcap,
                               backend="ref")
    b = fa_ops.flash_attention(q, k, v, window=window, softcap=softcap,
                               backend="interpret")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5, rtol=2e-5)


def test_flash_attention_blocks_smaller_than_seq():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S, H, hd = 1, 512, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    out = flash_attention_pallas(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                 v.swapaxes(1, 2), block_q=128, block_k=128,
                                 interpret=True).swapaxes(1, 2)
    ref = fa_ops.flash_attention(q, k, v, backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_causal_attention_and_its_vjp(dtype, tol):
    """The trainable causal kernel (splash, interpret mode) and its jnp
    reference against the model's masked f32-softmax attention, forward
    and the kernel's gradients of q, k and v, at MLA's head widths (qk 192,
    v 128) over two 1024-blocks, so the block past the causal frontier is
    skipped."""
    B, S, H = 2, 2048, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, S, H, 192), dtype)
    k = jax.random.normal(ks[1], (B, S, H, 192), dtype)
    v = jax.random.normal(ks[2], (B, S, H, 128), dtype)
    probe = jax.random.normal(ks[3], (B, S, H, 128), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))

    def reference(q, k, v):
        return L.sdpa_reference(q, k, v, q_pos=pos, k_pos=pos,
                                is_local=False, window=0, softcap=0.0)

    def kernel(q, k, v):
        return fa_ops.causal_attention(q, k, v, backend="interpret")

    def loss(fn):
        return lambda *a: jnp.sum(probe * fn(*a).astype(jnp.float32))

    def close(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        scale = np.abs(want).max()
        np.testing.assert_allclose(got / scale, want / scale, atol=tol)

    got = kernel(q, k, v)
    assert got.shape == (B, S, H, 128) and got.dtype == dtype
    close(got, reference(q, k, v))
    close(fa_ops.causal_attention(q, k, v, backend="ref"), reference(q, k, v))
    for a, b in zip(jax.grad(loss(kernel), (0, 1, 2))(q, k, v),
                    jax.grad(loss(reference), (0, 1, 2))(q, k, v)):
        close(a, b)


# --------------------------------------------------------------------------
# gram
# --------------------------------------------------------------------------

@pytest.mark.parametrize("r,m", [(100, 32), (1000, 300), (513, 129), (64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_shapes(r, m, dtype):
    a = jax.random.normal(jax.random.PRNGKey(0), (r, m), dtype)
    g_ref = gram_ref.gram_reference(a)
    g_pal = gram_ops.gram(a, backend="interpret")
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-3
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=tol * r ** 0.5, rtol=tol)


def test_gram_eigh_topk_matches_svd():
    a = jax.random.normal(jax.random.PRNGKey(1), (500, 80))
    U, s, V = gram_ops.gram_eigh_topk(a, 10, backend="ref")
    s_ref = np.linalg.svd(np.asarray(a), compute_uv=False)[:10]
    np.testing.assert_allclose(np.asarray(s), s_ref, rtol=1e-4)
    # U orthonormal, A V ~ U s
    np.testing.assert_allclose(np.asarray(U.T @ U), np.eye(10), atol=1e-4)
    np.testing.assert_allclose(np.asarray(a @ V), np.asarray(U * s[None, :]),
                               atol=1e-3)


# --------------------------------------------------------------------------
# rwkv6
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,K,V", [
    (1, 32, 2, 16, 16), (2, 64, 3, 16, 24), (1, 48, 1, 64, 64),
])
def test_wkv6_chunked_vs_scan(B, S, H, K, V):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, S, H, K))
    k = jax.random.normal(ks[1], (B, S, H, K))
    v = jax.random.normal(ks[2], (B, S, H, V))
    lw = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, S, H, K)), -8, 1.6))
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    o_scan = rwkv_ref.wkv6_scan(r, k, v, lw, u)
    o_chunk = rwkv_ref.wkv6_chunked(r, k, v, lw, u, chunk=16)
    np.testing.assert_allclose(np.asarray(o_chunk), np.asarray(o_scan),
                               atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("S", [32, 64, 80])   # incl. non-multiple of 16
def test_wkv6_pallas_interpret(S):
    B, H, K, V = 2, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r = jax.random.normal(ks[0], (B, S, H, K))
    k = jax.random.normal(ks[1], (B, S, H, K))
    v = jax.random.normal(ks[2], (B, S, H, V))
    lw = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, S, H, K)), -8, 1.6))
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    o_scan = rwkv_ref.wkv6_scan(r, k, v, lw, u)
    if S % 16 == 0:
        o_pal = rwkv_ops.wkv6(r, k, v, lw, u, backend="interpret")
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_scan),
                                   atol=2e-4, rtol=2e-3)
    o_chunk = rwkv_ops.wkv6(r, k, v, lw, u, backend="chunked")
    np.testing.assert_allclose(np.asarray(o_chunk), np.asarray(o_scan),
                               atol=2e-4, rtol=2e-3)


def test_wkv6_chunked_final_state():
    B, S, H, K, V = 1, 48, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r = jax.random.normal(ks[0], (B, S, H, K))
    k = jax.random.normal(ks[1], (B, S, H, K))
    v = jax.random.normal(ks[2], (B, S, H, V))
    lw = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, S, H, K)), -8, 1.6))
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    _, state = rwkv_ref.wkv6_chunked(r, k, v, lw, u, chunk=16,
                                     return_state=True)
    # evolve the exact scan one more step and compare the o produced from
    # the chunked state
    ks2 = jax.random.split(jax.random.PRNGKey(3), 4)
    r2 = jax.random.normal(ks2[0], (B, 1, H, K))
    k2 = jax.random.normal(ks2[1], (B, 1, H, K))
    v2 = jax.random.normal(ks2[2], (B, 1, H, V))
    lw2 = -jnp.exp(jnp.clip(jax.random.normal(ks2[3], (B, 1, H, K)), -8, 1.6))
    full = rwkv_ref.wkv6_scan(jnp.concatenate([r, r2], 1),
                              jnp.concatenate([k, k2], 1),
                              jnp.concatenate([v, v2], 1),
                              jnp.concatenate([lw, lw2], 1), u)
    kv = jnp.einsum("bhk,bhv->bhkv", k2[:, 0], v2[:, 0])
    o_next = jnp.einsum("bhk,bhkv->bhv", r2[:, 0],
                        state + u[None, :, :, None] * kv)
    np.testing.assert_allclose(np.asarray(o_next), np.asarray(full[:, -1]),
                               atol=2e-4, rtol=2e-3)


# --------------------------------------------------------------------------
# grouped matmul (the held experts' dropless products)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,sizes", [
    (256, 128, 256, (30, 0, 70, 50)),        # an empty group, rows past all
    (512, 256, 384, (512,)),                 # one group takes every row
    (300, 64, 128, (100, 100, 100)),         # rows not a tile multiple
])
def test_grouped_matmul_and_its_vjp(m, k, n, sizes):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32)
    rhs = jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    probe = jax.random.normal(ks[2], (m, n), jnp.float32)

    def loss(l, r, backend):
        return jnp.sum(probe * gmm_ops.grouped_matmul(l, r, gs,
                                                      backend=backend))

    got = gmm_ops.grouped_matmul(lhs, rhs, gs, backend="interpret")
    want = gmm_ref.grouped_matmul_reference(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)
    assert not np.any(np.asarray(got)[int(sum(sizes)):])
    g_got = jax.grad(loss, argnums=(0, 1))(lhs, rhs, "interpret")
    g_want = jax.grad(loss, argnums=(0, 1))(lhs, rhs, "ref")
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-4)


def test_grouped_matmul_vjp_under_vmap():
    """Member by member under vmap (the FedDCL silo axis), each member with
    its own group sizes."""
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    lhs = jax.random.normal(ks[0], (2, 128, 64), jnp.float32)
    rhs = jax.random.normal(ks[1], (2, 3, 64, 128), jnp.float32)
    gs = jnp.asarray([[40, 0, 60], [128, 0, 0]], jnp.int32)

    def grads(backend):
        def loss(l, r, g):
            return jnp.sum(jnp.sin(gmm_ops.grouped_matmul(l, r, g,
                                                          backend=backend)))
        return jax.vmap(jax.grad(loss, argnums=(0, 1)))(lhs, rhs, gs)

    for a, b in zip(grads("interpret"), grads("ref")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-4)
