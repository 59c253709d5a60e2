"""Batched device-resident collaboration engine: agreement of the batched
Gram / top-k / least-squares primitives with their NumPy oracles, and
host-vs-device agreement of the full protocol."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import collab
from repro.core.protocol import run_protocol
from repro.data.partition import split_iid
from repro.data.tabular import make_dataset, train_test_split
from repro.kernels.gram import ops as gram_ops, ref as gram_ref


# --------------------------------------------------------------------------
# gram_batched vs NumPy oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,r,m", [(1, 64, 16), (4, 300, 48), (7, 129, 65),
                                   (16, 512, 32)])
def test_gram_batched_ref_matches_numpy(B, r, m):
    rng = np.random.default_rng(0)
    a = rng.standard_normal((B, r, m)).astype(np.float32)
    g = np.asarray(gram_ops.gram_batched(jnp.asarray(a), backend="ref"))
    g_np = np.einsum("brm,brn->bmn", a, a)
    np.testing.assert_allclose(g, g_np, atol=5e-3 * r ** 0.5, rtol=5e-3)


@pytest.mark.parametrize("B,r,m", [(2, 100, 32), (3, 300, 48), (5, 513, 129)])
def test_gram_batched_pallas_interpret_matches_ref(B, r, m):
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((B, r, m)), jnp.float32)
    g_int = np.asarray(gram_ops.gram_batched(a, backend="interpret"))
    g_ref = np.asarray(gram_ref.gram_batched_reference(a))
    np.testing.assert_allclose(g_int, g_ref, atol=5e-3 * r ** 0.5, rtol=5e-3)


def test_gram_batched_matches_per_slice_gram():
    """The batched launch is exactly the stack of single-matrix launches."""
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((6, 200, 40)), jnp.float32)
    g_b = np.asarray(gram_ops.gram_batched(a, backend="ref"))
    for i in range(6):
        g_i = np.asarray(gram_ops.gram(a[i], backend="ref"))
        np.testing.assert_allclose(g_b[i], g_i, atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# batched top-k recovery
# --------------------------------------------------------------------------

def test_gram_eigh_topk_batched_matches_svd():
    rng = np.random.default_rng(3)
    a = rng.standard_normal((5, 400, 30)).astype(np.float32)
    U, s, V = gram_ops.gram_eigh_topk_batched(jnp.asarray(a), 8, backend="ref")
    U, s, V = np.asarray(U), np.asarray(s), np.asarray(V)
    for b in range(5):
        s_ref = np.linalg.svd(a[b], compute_uv=False)[:8]
        np.testing.assert_allclose(s[b], s_ref, rtol=1e-3)
        np.testing.assert_allclose(U[b].T @ U[b], np.eye(8), atol=1e-2)
        np.testing.assert_allclose(a[b] @ V[b], U[b] * s[b][None, :],
                                   atol=1e-2)


def test_gram_eigh_topk_batched_zero_padded_columns():
    """Zero-padded columns must stay in the null space: top-k pairs of the
    padded stack match the unpadded per-matrix SVDs."""
    rng = np.random.default_rng(4)
    widths = [10, 6, 14]
    mats = [rng.standard_normal((200, w)).astype(np.float32) for w in widths]
    padded, _ = collab.pad_ragged(mats)
    U, s, V = gram_ops.gram_eigh_topk_batched(jnp.asarray(padded), 5,
                                              backend="ref")
    for b, (A, w) in enumerate(zip(mats, widths)):
        s_ref = np.linalg.svd(A, compute_uv=False)[:5]
        np.testing.assert_allclose(np.asarray(s)[b], s_ref, rtol=1e-3)
        # V mass is confined to the real columns
        if w < padded.shape[2]:
            assert np.abs(np.asarray(V)[b, w:, :]).max() < 1e-4


# --------------------------------------------------------------------------
# batched eq. (3) (QR factors, then the triangular solve) vs np.linalg.lstsq
# over ragged widths
# --------------------------------------------------------------------------

def _solve_G_batched(a, z, col_mask=None, ridge=0.0):
    q, rr = gram_ops.solve_G_factor_batched(a, col_mask, ridge=ridge)
    return gram_ops.solve_G_from_factors(q, rr, z, col_mask)


@pytest.mark.parametrize("widths", [[6], [6, 3, 5, 2], [8, 8, 8],
                                    [1, 12, 4, 7, 2, 9]])
def test_solve_G_batched_matches_lstsq_ragged(widths):
    rng = np.random.default_rng(5)
    r, m_hat = 250, 4
    Z = rng.standard_normal((r, m_hat)).astype(np.float32)
    mats = [rng.standard_normal((r, w)).astype(np.float32) for w in widths]
    padded, mask = collab.pad_ragged(mats)
    G = np.asarray(_solve_G_batched(jnp.asarray(padded), jnp.asarray(Z),
                                    jnp.asarray(mask)))
    for b, (A, w) in enumerate(zip(mats, widths)):
        G_ref, *_ = np.linalg.lstsq(A, Z, rcond=None)
        np.testing.assert_allclose(G[b, :w], G_ref, atol=2e-3, rtol=2e-3)
        assert np.all(G[b, w:] == 0.0), "padded rows must be exactly zero"


def test_solve_G_batched_per_batch_targets():
    rng = np.random.default_rng(6)
    A = rng.standard_normal((3, 100, 8)).astype(np.float32)
    Z = rng.standard_normal((3, 100, 4)).astype(np.float32)
    G = np.asarray(_solve_G_batched(jnp.asarray(A), jnp.asarray(Z)))
    for b in range(3):
        G_ref, *_ = np.linalg.lstsq(A[b], Z[b], rcond=None)
        np.testing.assert_allclose(G[b], G_ref, atol=2e-3, rtol=2e-3)


def test_solve_G_all_device_matches_host():
    rng = np.random.default_rng(7)
    anchors = [rng.standard_normal((300, w)) for w in (5, 9, 3)]
    Z = rng.standard_normal((300, 4))
    G_host, _ = collab.solve_G_all(anchors, Z, backend="host")
    G_dev, _ = collab.solve_G_all(anchors, Z, backend="device")
    for gh, gd in zip(G_host, G_dev):
        assert gh.shape == gd.shape
        np.testing.assert_allclose(gd, gh, atol=2e-3, rtol=2e-3)


# --------------------------------------------------------------------------
# apply_G_all: batched per-user X̂ = X̃ G (step 12)
# --------------------------------------------------------------------------

def test_apply_G_all_device_matches_host_ragged_both_axes():
    """Users ragged in rows (n_j), G-input cols (m̃_j) AND G-output cols
    (m̂_j): the device path's single padded matmul must slice back to each
    user's exact host-product shape and values."""
    rng = np.random.default_rng(4)
    shapes = [(30, 6, 3), (17, 8, 5), (44, 4, 4)]       # (n_j, m̃_j, m̂_j)
    Xs = [rng.standard_normal((n, mt)) for n, mt, _ in shapes]
    Gs = [rng.standard_normal((mt, mh)) for _, mt, mh in shapes]
    host = collab.apply_G_all(Xs, Gs, backend="host")
    dev = collab.apply_G_all(Xs, Gs, backend="device")
    for h, dv, (n, mt, mh) in zip(host, dev, shapes):
        assert h.shape == dv.shape == (n, mh)
        np.testing.assert_allclose(dv, h, atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# full protocol: host vs device
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def partitions():
    ds = make_dataset("battery_small", n=900, seed=0)
    (Xtr, Ytr), _ = train_test_split(ds, 400, 400, seed=0)
    return Xtr, Ytr


@pytest.mark.parametrize("d,c", [(2, [2, 2]), (2, [1, 3]), (3, [1, 1, 1])])
def test_run_protocol_device_matches_host(partitions, d, c):
    Xtr, Ytr = partitions
    Xs, Ys = split_iid(Xtr, Ytr, d=d, c=c, n_ij=60, seed=0)
    host = run_protocol(Xs, Ys, m_tilde=4, anchor_r=600, seed=0,
                        svd_backend="host")
    dev = run_protocol(Xs, Ys, m_tilde=4, anchor_r=600, seed=0,
                       svd_backend="device")
    for Xh, Xd in zip(host.collab_X, dev.collab_X):
        rel = np.linalg.norm(Xh - Xd) / np.linalg.norm(Xh)
        assert rel <= 1e-3, rel
    rel_Z = np.linalg.norm(host.Z - dev.Z) / np.linalg.norm(host.Z)
    assert rel_Z <= 1e-3, rel_Z


def test_device_path_makes_zero_lstsq_calls(partitions, monkeypatch):
    """The acceptance criterion: no per-user Python-loop lstsq on device."""
    Xtr, Ytr = partitions
    Xs, Ys = split_iid(Xtr, Ytr, d=2, c=[2, 2], n_ij=60, seed=0)
    calls = []
    real = np.linalg.lstsq

    def counting_lstsq(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(np.linalg, "lstsq", counting_lstsq)
    run_protocol(Xs, Ys, m_tilde=4, anchor_r=600, seed=0,
                 svd_backend="device")
    assert calls == [], f"device path made {len(calls)} lstsq calls"
    # the counter sees the host reference's one lstsq (both backends now
    # answer eq. (3) from QR factors, which onboarding keeps)
    collab.solve_G(Xs[0][0], Xs[0][0][:, :1])
    assert len(calls) == 1, "the counter should see one lstsq"


def test_topk_svd_many_ragged_widths_match_host_clamp():
    """Per-matrix k clamp: a narrow group must not truncate wider groups'
    bases on the device backend (regression: global-min clamp)."""
    rng = np.random.default_rng(8)
    groups = [[rng.standard_normal((200, 8))],
              [rng.standard_normal((200, 16)), rng.standard_normal((200, 16))]]
    for m_hat in (4, 16):
        host, _ = collab.intra_group_bases(groups, m_hat, seeds=[0, 1],
                                           backend="host")
        dev, _ = collab.intra_group_bases(groups, m_hat, seeds=[0, 1],
                                          backend="device")
        assert [b.B.shape for b in host] == [b.B.shape for b in dev]
        for bh, bd in zip(host, dev):
            rel = np.linalg.norm(bh.B - bd.B) / np.linalg.norm(bh.B)
            assert rel <= 1e-3, rel


def test_solve_G_batched_ridge_bounds_rank_deficient():
    """QR needs full-column-rank anchors; ridge > 0 is the documented escape
    hatch that keeps degenerate (collinear-column) solves bounded."""
    rng = np.random.default_rng(9)
    A = rng.standard_normal((200, 6)).astype(np.float32)
    A[:, 3] = A[:, 2]                       # exactly collinear pair
    Z = rng.standard_normal((200, 4)).astype(np.float32)
    G = np.asarray(_solve_G_batched(jnp.asarray(A[None]),
                                    jnp.asarray(Z), ridge=1e-3))[0]
    assert np.all(np.isfinite(G))
    assert np.abs(G).max() < 1e3
    # residual still ~ least-squares quality
    res = np.linalg.norm(A @ G - Z)
    G_ls, *_ = np.linalg.lstsq(A, Z, rcond=None)
    res_ls = np.linalg.norm(A @ G_ls - Z)
    assert res < res_ls * 1.01
    # and ridge leaves well-conditioned solves essentially unchanged
    B = rng.standard_normal((200, 6)).astype(np.float32)
    G_r = np.asarray(_solve_G_batched(jnp.asarray(B[None]),
                                      jnp.asarray(Z), ridge=1e-3))[0]
    G_0, *_ = np.linalg.lstsq(B, Z, rcond=None)
    np.testing.assert_allclose(G_r, G_0, atol=5e-3, rtol=5e-3)


def test_get_backend_names():
    assert collab.get_backend("host").name == "host"
    assert collab.get_backend("device").name == "device"
    assert collab.get_backend("tpu").name == "device"   # legacy alias
    with pytest.raises(ValueError):
        collab.get_backend("gpu-madeup")
