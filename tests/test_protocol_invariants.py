"""Protocol invariants that must hold on BOTH collaboration backends:
the paper's exactly-two-communications claim and Theorem-1 exact alignment
when m̂ ≤ rank(Ã)."""
import numpy as np
import pytest

from repro.core import collab
from repro.core.protocol import finalize_user_models, run_protocol
from repro.data.partition import split_iid
from repro.data.tabular import make_dataset, train_test_split

BACKENDS = ["host", "device"]


@pytest.fixture(scope="module")
def partitions():
    ds = make_dataset("battery_small", n=900, seed=0)
    (Xtr, Ytr), _ = train_test_split(ds, 400, 400, seed=0)
    return split_iid(Xtr, Ytr, d=2, c=[2, 2], n_ij=80, seed=0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_user_round_trips_exactly_two(partitions, backend):
    Xs, Ys = partitions
    setup = run_protocol(Xs, Ys, m_tilde=4, anchor_r=600, seed=0,
                         svd_backend=backend)
    finalize_user_models(setup, h=lambda z: z)
    trips = setup.comm.user_round_trips()
    assert len(trips) == 4
    assert all(v == 2 for v in trips.values()), trips


@pytest.mark.parametrize("backend", BACKENDS)
def test_theorem1_alignment_residual_near_zero(backend):
    """Same-range maps (shared fixed W) + m̂ = m̃ ≤ rank(Ã): eq. (3) is
    solvable exactly, so the alignment residual vanishes (fp32 on device)."""
    rng = np.random.default_rng(0)
    d, c, n_ij, m, m_tilde = 3, 2, 40, 12, 5
    X = rng.standard_normal((d * c * n_ij, m))
    Y = rng.standard_normal((d * c * n_ij, 1))
    Xs = [[X[(i * c + j) * n_ij:(i * c + j + 1) * n_ij] for j in range(c)]
          for i in range(d)]
    # zero per-user means so the fitted maps f_j(x) = (x − μ_j) W share one
    # exact range (Theorem 1's same-function-range condition)
    Xs = [[x - x.mean(axis=0, keepdims=True) for x in g] for g in Xs]
    Ys = [[Y[(i * c + j) * n_ij:(i * c + j + 1) * n_ij] for j in range(c)]
          for i in range(d)]
    W = rng.standard_normal((m, m_tilde))
    setup = run_protocol(Xs, Ys, m_tilde=m_tilde, anchor_r=500,
                         mapping_kind="fixed", fixed_W=W, seed=0,
                         svd_backend=backend)
    tol = 1e-8 if backend == "host" else 1e-4
    for i in range(d):
        for j in range(c):
            A_ij = setup.mappings[i][j](setup.anchor)
            res = collab.alignment_residual(A_ij, setup.Gs[i][j], setup.Z)
            assert res < tol, (backend, i, j, res)


@pytest.mark.parametrize("backend", BACKENDS)
def test_collab_layer_theorem1_direct(backend):
    """Same invariant exercised through the collab-layer API (batched
    intra_group_bases + solve_G_all) rather than run_protocol."""
    rng = np.random.default_rng(1)
    d, c, m_tilde, r = 2, 3, 4, 400
    F = rng.standard_normal((10, m_tilde))
    anchor = rng.standard_normal((r, 10))
    groups = [[anchor @ F @ (rng.standard_normal((m_tilde, m_tilde)) +
                             np.eye(m_tilde) * m_tilde)
               for _ in range(c)] for _ in range(d)]
    bases, _ = collab.intra_group_bases(groups, m_tilde,
                                        seeds=[7 * i for i in range(d)],
                                        backend=backend)
    target = collab.central_target(bases, m_tilde, seed=99, backend=backend)
    flat = [a for g in groups for a in g]
    Gs, _ = collab.solve_G_all(flat, target.Z, backend=backend)
    tol = 1e-6 if backend == "host" else 1e-3
    for A, G in zip(flat, Gs):
        assert collab.alignment_residual(A, G, target.Z) < tol
