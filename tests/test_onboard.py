"""Incremental onboarding == from-scratch protocol recompute (DESIGN.md §10).

The whole point of the blocked-Gram / cached-factor path is that admitting
a tenant onto a live deployment produces THE SAME collaboration solve a
full `run_protocol` over all tenants would — the only thing shared is the
anchor (fixed once at deployment, passed via `run_protocol(anchor=...)`).
Property-style sweep: ragged per-user shapes, several group layouts, both
backends, user- and silo-onboarding, and repeated onboarding (error must
not compound past the bar).

Bars: 1e-8 for the host backend (both paths are f64 LAPACK — agreement is
near-exact), 1e-5 for the device backend (fp32 Gram/eigh/QR arithmetic).
"""
import os

import numpy as np
import pytest

from repro.core import collab, protocol

BACKENDS = [("host", 1e-8), ("device", 1e-5)]


def _mkdata(rng, counts, m, lo=20, hi=45):
    Xs = [[rng.standard_normal((int(rng.integers(lo, hi)), m))
           for _ in range(c)] for c in counts]
    Ys = [[rng.standard_normal((x.shape[0], 1)) for x in row] for row in Xs]
    return Xs, Ys


def _assert_setups_match(inc, ref, tol):
    """Incremental setup vs from-scratch reference: Z, every G, every X̂."""
    scale = max(1.0, float(np.abs(ref.Z).max()))
    assert np.abs(np.asarray(inc.Z) - np.asarray(ref.Z)).max() / scale < tol
    assert inc.num_groups == ref.num_groups
    for i in range(ref.num_groups):
        assert inc.num_users(i) == ref.num_users(i)
        for j in range(ref.num_users(i)):
            g_inc, g_ref = np.asarray(inc.Gs[i][j]), np.asarray(ref.Gs[i][j])
            s = max(1.0, float(np.abs(g_ref).max()))
            assert np.abs(g_inc - g_ref).max() / s < tol, (i, j)
        x_inc, x_ref = np.asarray(inc.collab_X[i]), np.asarray(ref.collab_X[i])
        assert x_inc.shape == x_ref.shape
        s = max(1.0, float(np.abs(x_ref).max()))
        assert np.abs(x_inc - x_ref).max() / s < tol, i
        np.testing.assert_allclose(inc.collab_Y[i], ref.collab_Y[i])


@pytest.mark.parametrize("backend,tol", BACKENDS)
@pytest.mark.parametrize("counts", [[2, 3], [3, 1, 2]])
def test_onboard_user_matches_full_recompute(backend, tol, counts):
    rng = np.random.default_rng(hash((backend, len(counts))) % 2**31)
    m = 7
    Xs, Ys = _mkdata(rng, counts, m)
    Xn = rng.standard_normal((33, m))
    Yn = rng.standard_normal((33, 1))
    kw = dict(m_tilde=4, anchor_r=120, seed=3, svd_backend=backend)

    setup = protocol.run_protocol(Xs, Ys, onboard=True, **kw)
    tgt = int(rng.integers(0, len(counts)))
    j = setup.onboard_user(tgt, Xn, Yn)
    assert j == counts[tgt]

    Xs2 = [list(row) for row in Xs]
    Ys2 = [list(row) for row in Ys]
    Xs2[tgt].append(Xn)
    Ys2[tgt].append(Yn)
    ref = protocol.run_protocol(Xs2, Ys2, anchor=setup.anchor, **kw)
    _assert_setups_match(setup, ref, tol)


@pytest.mark.parametrize("backend,tol", BACKENDS)
def test_onboard_silo_matches_full_recompute(backend, tol):
    rng = np.random.default_rng(11)
    m = 6
    Xs, Ys = _mkdata(rng, [2, 2], m)
    Xn = [rng.standard_normal((int(rng.integers(25, 40)), m))
          for _ in range(3)]
    Yn = [rng.standard_normal((x.shape[0], 1)) for x in Xn]
    kw = dict(m_tilde=4, anchor_r=100, seed=0, svd_backend=backend)

    setup = protocol.run_protocol(Xs, Ys, onboard=True, **kw)
    i = setup.onboard_silo(Xn, Yn)
    assert i == 2

    ref = protocol.run_protocol(list(Xs) + [Xn], list(Ys) + [Yn],
                                anchor=setup.anchor, **kw)
    _assert_setups_match(setup, ref, tol)


@pytest.mark.parametrize("backend,tol", BACKENDS)
def test_onboard_silo_fitted_concurrently_equals_one_at_a_time(
        monkeypatch, backend, tol):
    """A 3-user silo's step 2 runs in the thread pool when the process
    may use several cores; maps, G's, X̂ and the upload log equal those
    of the one-core run, which fits the users one at a time."""
    rng = np.random.default_rng(12)
    m = 6
    Xs, Ys = _mkdata(rng, [2, 2], m)
    Xn = [rng.standard_normal((int(rng.integers(25, 40)), m))
          for _ in range(3)]
    Yn = [rng.standard_normal((x.shape[0], 1)) for x in Xn]
    kw = dict(m_tilde=4, anchor_r=100, seed=2, svd_backend=backend)
    setups = []
    for cores in (1, 8):
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda pid, n=cores: set(range(n)))
        setup = protocol.run_protocol(Xs, Ys, onboard=True, **kw)
        assert setup.onboard_silo(Xn, Yn) == 2
        setups.append(setup)
    pooled, serial = setups[1], setups[0]
    _assert_setups_match(pooled, serial, tol)
    assert pooled.comm.events == serial.comm.events
    for f, rf in zip(pooled.mappings[2], serial.mappings[2]):
        np.testing.assert_allclose(f.mu, rf.mu, rtol=1e-12)
        assert np.abs(f.W - rf.W).max() < 1e-12


@pytest.mark.parametrize("backend,tol", BACKENDS)
def test_repeated_onboarding_does_not_drift(backend, tol):
    """user, user, silo, user onto the growing deployment — the final state
    must still match ONE from-scratch solve (errors must not compound)."""
    rng = np.random.default_rng(21)
    m = 5
    Xs, Ys = _mkdata(rng, [2, 2], m)
    kw = dict(m_tilde=3, anchor_r=90, seed=7, svd_backend=backend)
    setup = protocol.run_protocol(Xs, Ys, onboard=True, **kw)
    Xs2 = [list(r) for r in Xs]
    Ys2 = [list(r) for r in Ys]

    def new(n):
        return rng.standard_normal((n, m)), rng.standard_normal((n, 1))

    for tgt in (0, 1):
        x, y = new(int(rng.integers(20, 35)))
        setup.onboard_user(tgt, x, y)
        Xs2[tgt].append(x)
        Ys2[tgt].append(y)
    silo = [new(int(rng.integers(20, 35))) for _ in range(2)]
    setup.onboard_silo([x for x, _ in silo], [y for _, y in silo])
    Xs2.append([x for x, _ in silo])
    Ys2.append([y for _, y in silo])
    x, y = new(28)
    setup.onboard_user(2, x, y)                 # onto the onboarded silo
    Xs2[2].append(x)
    Ys2[2].append(y)

    ref = protocol.run_protocol(Xs2, Ys2, anchor=setup.anchor, **kw)
    _assert_setups_match(setup, ref, tol)


@pytest.mark.parametrize("backend,tol", BACKENDS)
@pytest.mark.parametrize("counts", [[2, 2, 2], [3, 1, 2]],
                         ids=["equal_widths", "ragged_widths"])
def test_onboard_state_equals_recomputed_state(backend, tol, counts):
    """The state run_protocol(onboard=True) keeps is step 3's own Grams
    and QR factors; it equals the state recomputed group by group: each
    group's Gram reduced alone, and its users' G solved from factors of
    the group alone (a ragged roster's runs carry the roster's pad
    width, which the mask makes a no-op)."""
    rng = np.random.default_rng(sum(counts) * len(counts))
    Xs, Ys = _mkdata(rng, counts, 7)
    setup = protocol.run_protocol(Xs, Ys, onboard=True, m_tilde=4,
                                  anchor_r=120, seed=5, svd_backend=backend)
    st, be = setup.onboard, collab.get_backend(backend)
    assert len(st.grams) == len(st.g_factors) == len(counts)
    for i, row in enumerate(st.inter_A):
        want = be.gram(np.concatenate(row, axis=1))
        assert st.grams[i].shape == want.shape
        assert st.grams[i].dtype == want.dtype
        np.testing.assert_array_equal(st.grams[i], want)
        kept = be.solve_G_factors(st.g_factors[i], setup.Z)
        alone = be.solve_G_factors(be.factor_G_many(row), setup.Z)
        assert len(kept) == len(alone) == counts[i]
        for g_kept, g_alone in zip(kept, alone):
            assert g_kept.shape == g_alone.shape
            s = max(1.0, float(np.abs(g_alone).max()))
            assert np.abs(g_kept - g_alone).max() / s < tol, i


def test_onboard_requires_state():
    rng = np.random.default_rng(0)
    Xs, Ys = _mkdata(rng, [2], 5)
    setup = protocol.run_protocol(Xs, Ys, m_tilde=3, anchor_r=60, seed=0)
    with pytest.raises(RuntimeError, match="onboard=True"):
        setup.onboard_user(0, Xs[0][0], Ys[0][0])


def test_onboarded_comm_cost_is_one_round_trip():
    """The newcomer uploads its anchor image once and (conceptually)
    downloads the model once — exactly the paper's 2-communication claim;
    incumbents must not re-communicate."""
    rng = np.random.default_rng(4)
    Xs, Ys = _mkdata(rng, [2, 2], 5)
    setup = protocol.run_protocol(Xs, Ys, m_tilde=3, anchor_r=60, seed=0,
                                  onboard=True)
    n_events = len(setup.comm.events)
    setup.onboard_user(0, rng.standard_normal((25, 5)),
                       rng.standard_normal((25, 1)))
    new_events = setup.comm.events[n_events:]
    uploads = [e for e in new_events if e.src.startswith("user")]
    # exactly one user-originated upload: the newcomer's intermediates —
    # incumbents communicate nothing (their f_j never re-fits)
    assert len(uploads) == 1
    assert uploads[0].src == "user(0,2)"
