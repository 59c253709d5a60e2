"""Step 2 fitted concurrently (`mappings.fit_maps`) == one user at a time.

Each user's map depends only on its own data and seed, so fitting the
users in a thread pool, with OpenBLAS held to cores // workers threads,
must give what `fit_mapping` and the two products give one user at a time:
to 1e-12 relative, with no singular vector's sign flipped (a flip would
turn G_j into M^T G_j). The pool's width follows the cores the process may
use; the tests force it by what `os.sched_getaffinity` reports.
"""
import os

import jax
import numpy as np
import pytest
from threadpoolctl import threadpool_info

from repro import obs
from repro.core import mappings, protocol
from repro.core.mappings import fit_mapping, fit_maps

COUNTER = "feddcl.protocol.step2_concurrent_maps"
GROUPS = (1, 3, 4)          # ragged users per group
M, M_TILDE = 12, 5
KINDS = ["pca_rot", "pca", "randproj", "fixed"]
CORES = [1, 2, 64]          # inline; fewer workers than users; one each


def _users(seed=0):
    rng = np.random.default_rng(seed)
    Xs = [[rng.standard_normal((int(rng.integers(30, 60)), M)) * 3 + 1
           for _ in range(c)] for c in GROUPS]
    Ys = [[rng.integers(0, 3, x.shape[0]) for x in row] for row in Xs]
    return Xs, Ys, rng.standard_normal((80, M))


def _force_cores(monkeypatch, n):
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(n)))


def _blas_threads():
    return {lib["num_threads"] for lib in threadpool_info()
            if lib["user_api"] == "blas"}


def _rel(a, b):
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300)


@pytest.mark.parametrize("cores", CORES)
@pytest.mark.parametrize("kind", KINDS)
def test_fit_maps_equals_one_user_at_a_time(monkeypatch, kind, cores):
    Xs, _, anchor = _users()
    flat = [x for row in Xs for x in row]
    seeds = [7 * 1009 + i * 101 + j for i, c in enumerate(GROUPS)
             for j in range(c)]
    W = np.random.default_rng(1).standard_normal((M, M_TILDE))
    want = []
    for X, s in zip(flat, seeds):
        f = fit_mapping(kind, X, M_TILDE, seed=s, W=W)
        want.append((f, f(X), f(anchor)))

    _force_cores(monkeypatch, cores)
    got = fit_maps(kind, flat, anchor, M_TILDE, seeds, W=W)

    assert len(got) == len(want)
    for (f, Xt, At), (rf, rXt, rAt) in zip(got, want):
        if cores == 1:                  # the inline path is the serial one
            np.testing.assert_array_equal(f.W, rf.W)
            np.testing.assert_array_equal(Xt, rXt)
            np.testing.assert_array_equal(At, rAt)
        for a, b in ((f.mu, rf.mu), (f.W, rf.W), (Xt, rXt), (At, rAt)):
            assert a.shape == b.shape and a.dtype == np.float64
            assert _rel(a, b) < 1e-12
        assert (np.einsum("mk,mk->k", f.W, rf.W) > 0).all()


@pytest.mark.parametrize("cores", CORES[1:])
def test_run_protocol_comm_log_and_outputs_match_the_serial_run(monkeypatch,
                                                                cores):
    Xs, Ys, anchor = _users(2)
    kw = dict(m_tilde=M_TILDE, anchor=anchor, seed=4, svd_backend="host")
    _force_cores(monkeypatch, 1)
    serial = protocol.run_protocol(Xs, Ys, **kw)
    _force_cores(monkeypatch, cores)
    pooled = protocol.run_protocol(Xs, Ys, **kw)

    assert pooled.comm.events == serial.comm.events
    assert [e.src for e in pooled.comm.events[:sum(GROUPS)]] == [
        f"user({i},{j})" for i, c in enumerate(GROUPS) for j in range(c)]
    for i, c in enumerate(GROUPS):
        for j in range(c):
            assert _rel(pooled.mappings[i][j].W, serial.mappings[i][j].W) < 1e-12
            assert _rel(pooled.Gs[i][j], serial.Gs[i][j]) < 1e-12
        assert _rel(pooled.collab_X[i], serial.collab_X[i]) < 1e-12


@pytest.mark.parametrize("fails", [False, True])
def test_blas_limit_holds_in_the_pool_and_is_restored(monkeypatch, fails):
    Xs, _, anchor = _users(3)
    flat = [x for row in Xs for x in row]          # 8 users
    if fails:                   # one user's data of another width: f(anchor)
        flat[5] = flat[5][:, :M - 1]               # raises in its worker
    seen = []

    def recording_fit(*a, **k):
        seen.append(_blas_threads())
        return fit_mapping(*a, **k)

    monkeypatch.setattr(mappings, "fit_mapping", recording_fit)
    _force_cores(monkeypatch, 4)                   # 4 workers, 1 thread each
    before = _blas_threads()
    if fails:
        with pytest.raises(ValueError):
            fit_maps("pca_rot", flat, anchor, M_TILDE, range(len(flat)))
    else:
        fit_maps("pca_rot", flat, anchor, M_TILDE, range(len(flat)))
    assert _blas_threads() == before
    assert seen == [{1}] * len(flat)


@pytest.mark.parametrize("n_users,cores,want", [(5, 4, 5), (1, 4, 0),
                                                (5, 1, 0)])
def test_counter_reads_the_maps_fitted_in_the_pool(monkeypatch, tmp_path,
                                                   n_users, cores, want):
    Xs, _, anchor = _users(4)
    flat = [x for row in Xs for x in row][:n_users]
    _force_cores(monkeypatch, cores)
    obs.clear()
    with jax.profiler.trace(str(tmp_path)):
        fit_maps("pca_rot", flat, anchor, M_TILDE, range(n_users))
    assert obs.snapshot()["counters"].get(COUNTER, 0) == want
    obs.clear()
