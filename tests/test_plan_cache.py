"""Plan-cache tier (DESIGN.md §6): compiled executables shared across
tenants.

Warm hits must agree with cold runs, the counters must record exactly the
executables built, distinct configs (aggregator / reset_opt / fedprox_mu)
must never alias onto one plan, and a cached run must agree with the host
engine on the SAME bucketed layout. Also covers the FedDCL.fit() facade
and the persistent XLA compilation cache wiring.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import federated
from repro.core.federated import (PlanCache, bucket_pow2, pad_silo_data,
                                  run_federated)
from repro.models import mlp
from repro.optim import adamw

M = 6          # raw feature dim of the toy tenants


def _silos(d, n, seed=0):
    r = np.random.default_rng(seed)
    wt = r.standard_normal((M, 1))
    out = []
    for i in range(d):
        X = r.standard_normal((n + 3 * i, M))            # ragged on purpose
        out.append((X, X @ wt + 0.01 * r.standard_normal((n + 3 * i, 1))))
    return out


def _params(seed=0):
    return mlp.init_mlp_params(jax.random.PRNGKey(seed), M, (8,), 1)


def _loss(p, x, y):
    return mlp.mlp_per_example_loss(p, x, y, "regression")


KW = dict(rounds=2, local_epochs=1, batch_size=8, engine="scan",
          loss_id=("mlp_per_example_loss", "regression"),
          opt_id=("adamw", 1e-2))


def _run(silos, cache, **over):
    kw = {**KW, **over}
    return run_federated(_loss, _params(), silos, opt=adamw(1e-2),
                         cache=cache, **kw)


def _flat(result):
    return np.concatenate(
        [np.ravel(np.asarray(l)) for l in jax.tree.leaves(result.params)])


# ---------------------------------------------------------------------------
# correctness: warm == cold, cached scan == host on the bucketed layout
# ---------------------------------------------------------------------------

def test_warm_hit_agrees_with_cold_run():
    cache = PlanCache()
    first = _run(_silos(3, 20, seed=0), cache)
    assert first.cache_stats["hit"] is False
    tenant = _silos(3, 22, seed=1)           # new tenant, same shape bucket
    warm = _run(tenant, cache)
    assert warm.cache_stats["hit"] is True
    cold = _run(tenant, PlanCache())         # fresh cache: full rebuild
    assert cold.cache_stats["hit"] is False
    np.testing.assert_allclose(_flat(warm), _flat(cold), rtol=1e-6, atol=1e-7)
    assert warm.history[-1]["loss"] == pytest.approx(
        cold.history[-1]["loss"], rel=1e-5)


def test_cached_scan_matches_host_on_bucketed_layout():
    silos = _silos(3, 20, seed=0)
    res = _run(silos, PlanCache())
    bs = KW["batch_size"]
    n_max = max(x.shape[0] for x, _ in silos)
    padded = pad_silo_data(silos, bs,
                           min_batches=bucket_pow2(-(-n_max // bs)),
                           min_silos=bucket_pow2(len(silos)))
    batch_loss = federated._make_batch_loss(_loss, True, 0.0)
    host = federated._run_host(
        batch_loss, _params(), padded, opt=adamw(1e-2), rounds=KW["rounds"],
        local_epochs=KW["local_epochs"], aggregator="fedavg", seed=0,
        eval_fn=None, per_example=True, reset_opt=True)
    np.testing.assert_allclose(_flat(res), _flat(host), rtol=1e-4, atol=1e-5)


def test_warm_plan_cache_hit_compiles_nothing():
    """The direct claim behind the cache tier: a warm hit builds ZERO new
    executables (CompileCounter patches the backend compiler, so this can't
    be fooled by fast-but-recompiling paths the old timing checks missed)."""
    from repro.analysis import CompileCounter

    cache = PlanCache()
    tenant = _silos(3, 20, seed=0)
    _run(tenant, cache)                          # cold: builds + warms jits
    with CompileCounter() as cc:
        warm = _run(_silos(3, 20, seed=1), cache)    # same shapes, new data
    assert warm.cache_stats["hit"] is True
    assert cc.count == 0, f"warm cache hit compiled {cc.count} modules"


# ---------------------------------------------------------------------------
# counters, bucket sharing, aliasing, eviction
# ---------------------------------------------------------------------------

def test_counters_and_bucket_sharing():
    cache = PlanCache()
    r1 = _run(_silos(3, 20, seed=0), cache)      # d=3 -> silo bucket 4
    r2 = _run(_silos(4, 18, seed=1), cache)      # d=4 -> same bucket, hits
    assert cache.stats() == {"hits": 1, "misses": 1, "evictions": 0,
                             "plans": 1}
    assert r1.cache_stats["hit"] is False and r2.cache_stats["hit"] is True


def test_distinct_configs_never_alias():
    cache = PlanCache()
    silos = _silos(3, 20, seed=0)
    base = _run(silos, cache)
    prox = _run(silos, cache, aggregator="fedprox", fedprox_mu=0.1)
    carry = _run(silos, cache, reset_opt_per_round=False)
    s = cache.stats()
    assert s["misses"] == 3 and s["hits"] == 0 and s["plans"] == 3
    again = _run(silos, cache)                   # base config now hits
    assert again.cache_stats["hit"] is True
    np.testing.assert_allclose(_flat(again), _flat(base), rtol=1e-6)
    # the three configs genuinely train differently — aliasing would
    # silently collapse them onto one executable
    assert not np.allclose(_flat(base), _flat(prox))
    assert not np.allclose(_flat(base), _flat(carry))


def test_lru_eviction():
    cache = PlanCache(max_plans=1)
    _run(_silos(2, 10, seed=0), cache)           # bucket (2 silos, 2 batches)
    _run(_silos(3, 20, seed=1), cache)           # bucket (4, 4) -> evicts
    assert cache.stats()["evictions"] == 1 and len(cache) == 1
    r = _run(_silos(2, 10, seed=0), cache)       # evicted -> rebuilds
    assert r.cache_stats["hit"] is False


def test_mesh_enters_plan_key_and_never_aliases():
    """A sharded and an unsharded plan over the same bucketed layout must
    be distinct cache entries (their executables differ: shard_map + psums
    vs plain vmap), while two runs on the SAME mesh share one."""
    from repro.launch.mesh import make_host_mesh

    cache = PlanCache()
    silos = _silos(3, 20, seed=0)
    base = _run(silos, cache)
    mesh = make_host_mesh(model=1)
    sharded = _run(silos, cache, mesh=mesh)
    assert sharded.cache_stats["hit"] is False        # no alias
    again = _run(_silos(3, 22, seed=1), cache, mesh=mesh)
    assert again.cache_stats["hit"] is True           # same mesh -> hit
    s = cache.stats()
    assert s["plans"] == 2 and s["misses"] == 2 and s["hits"] == 1
    np.testing.assert_allclose(_flat(base), _flat(sharded),
                               rtol=1e-5, atol=1e-6)


def test_chunk_mode_plan_is_rounds_agnostic():
    """With eval_fn the cached plan is the streamed chunk step, which never
    bakes `rounds` into the executable — a rounds=3 and a rounds=5 run
    share ONE plan (the win that makes rounds≫10 configs cacheable)."""
    cache = PlanCache()
    silos = _silos(3, 20, seed=0)
    ev = lambda p: {"w0": float(np.asarray(
        jax.tree.leaves(p)[0]).ravel()[0])}
    r3 = _run(silos, cache, rounds=3, eval_fn=ev)
    r5 = _run(silos, cache, rounds=5, eval_fn=ev)
    assert r3.cache_stats["hit"] is False
    assert r5.cache_stats["hit"] is True
    assert cache.stats()["plans"] == 1
    assert len(r3.history) == 3 and len(r5.history) == 5
    # the shared executable still trains: prefixes agree round-for-round
    for a, b in zip(r3.history, r5.history):
        assert a["loss"] == pytest.approx(b["loss"], rel=1e-6)
        assert a["w0"] == pytest.approx(b["w0"], rel=1e-6)


def test_cache_requires_scan_engine():
    with pytest.raises(ValueError):
        _run(_silos(2, 10), PlanCache(), engine="host")


# ---------------------------------------------------------------------------
# sample counts stay integral (float32 counts corrupt above 2^24)
# ---------------------------------------------------------------------------

def test_sample_counts_stay_integral():
    padded = pad_silo_data(_silos(3, 20), 8, min_silos=4)
    assert np.issubdtype(padded.sizes.dtype, np.integer)
    assert padded.sizes.tolist() == [20, 23, 26, 0]   # bucket silo: size 0
    big = np.array([2 ** 24 + 1, 2 ** 24], np.int64)
    # the hazard the integral dtype guards against:
    assert np.float32(big[0]) == np.float32(big[1])
    # float64 normalization keeps the order; the cast happens only after
    w64 = np.asarray(big, np.float64)
    w64 /= w64.sum()
    assert w64[0] > w64[1]
    w = federated._norm_weights(big)
    assert w.dtype == np.float32
    assert abs(float(w.sum()) - 1.0) < 1e-6
    np.testing.assert_allclose(federated._norm_weights(np.array([1, 3])),
                               [0.25, 0.75], rtol=0)


# ---------------------------------------------------------------------------
# the FedDCL.fit() facade rides the same cache
# ---------------------------------------------------------------------------

def _groups(n_ij, seed):
    r = np.random.default_rng(seed)
    w = r.standard_normal((M, 1))
    Xs = [[r.standard_normal((n_ij, M)) for _ in range(2)] for _ in range(2)]
    Ys = [[x @ w + 0.01 * r.standard_normal((n_ij, 1)) for x in g]
          for g in Xs]
    return Xs, Ys


def test_api_fit_reuses_executables_across_tenants():
    from repro.api import FedDCL
    from repro.core.federated import default_plan_cache

    default_plan_cache().clear()
    m1 = FedDCL(m_tilde=4, anchor_r=64, rounds=2, local_epochs=1, seed=0)
    _, res1 = m1.fit(*_groups(20, 0))
    assert res1.cache_stats["hit"] is False
    # a fresh estimator on a new same-bucket tenant hits the shared cache
    m2 = FedDCL(m_tilde=4, anchor_r=64, rounds=2, local_epochs=1, seed=1)
    Xs2, Ys2 = _groups(24, 1)
    setup2, res2 = m2.fit(Xs2, Ys2)
    assert res2.cache_stats["hit"] is True
    assert default_plan_cache().stats()["misses"] == 1
    yhat = m2.predict(Xs2[0][0])
    assert yhat.shape == (24, 1) and np.all(np.isfinite(yhat))
    assert np.isfinite(m2.score(Xs2[0][0], Ys2[0][0]))
    assert setup2.collab_X[0].shape[1] == 4


def test_api_fit_warm_path_compiles_nothing():
    """End-to-end recompile sentinel: a second same-shape tenant through
    FedDCL.fit() must not build a single executable — the FL plan comes
    from the shared PlanCache and every collab-phase jit re-hits its trace
    cache (tenants must share shapes: a different n would legitimately
    recompile the collab projections)."""
    from repro.analysis import CompileCounter
    from repro.api import FedDCL
    from repro.core.federated import default_plan_cache

    default_plan_cache().clear()
    m1 = FedDCL(m_tilde=4, anchor_r=64, rounds=2, local_epochs=1, seed=0)
    m1.fit(*_groups(20, 0))
    m2 = FedDCL(m_tilde=4, anchor_r=64, rounds=2, local_epochs=1, seed=1)
    with CompileCounter() as cc:
        _, res2 = m2.fit(*_groups(20, 1))
    assert res2.cache_stats["hit"] is True
    assert cc.count == 0, f"warm fit() compiled {cc.count} modules"


# ---------------------------------------------------------------------------
# persistent XLA compilation cache wiring
# ---------------------------------------------------------------------------

@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    from repro import api

    prev_dir = jax.config.jax_compilation_cache_dir
    prev = api._COMPILE_CACHE_DIR
    yield api
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    api._COMPILE_CACHE_DIR = prev
    compilation_cache.reset_cache()


def test_persistent_compilation_cache_populates(tmp_path,
                                                restore_cache_config):
    """A directory JAX was given (JAX_COMPILATION_CACHE_DIR lands in this
    config) stands: the cache is written there and nowhere else."""
    api = restore_cache_config
    d = str(tmp_path / "xla")
    jax.config.update("jax_compilation_cache_dir", d)
    assert api.enable_persistent_compilation_cache() == d
    assert api.enable_persistent_compilation_cache() == d   # idempotent
    assert jax.config.jax_compilation_cache_dir == d
    f = jax.jit(lambda x: jnp.tanh(x * 2.0) @ x.T)
    f(jnp.arange(32.0).reshape(4, 8)).block_until_ready()
    assert os.listdir(d), "compilation cache dir stayed empty"


def test_compilation_cache_defaults_to_fixed_checkout_path(
        restore_cache_config):
    """Without a JAX setting the cache goes to ONE fixed path inside the
    checkout — never a temporary or per-process directory."""
    import pathlib

    api = restore_cache_config
    jax.config.update("jax_compilation_cache_dir", None)
    api._COMPILE_CACHE_DIR = None
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert api.DEFAULT_COMPILATION_CACHE_DIR == str(repo / ".jax_cache")
    assert (api.enable_persistent_compilation_cache()
            == api.DEFAULT_COMPILATION_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == \
        api.DEFAULT_COMPILATION_CACHE_DIR


def test_compilation_cache_env_var_is_respected(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set from outside wins, in a fresh process."""
    import pathlib
    import subprocess
    import sys

    d = str(tmp_path / "outside")
    r = subprocess.run(
        [sys.executable, "-c",
         "from repro.api import enable_persistent_compilation_cache as e;"
         "print(e())"],
        capture_output=True, text=True, timeout=300,
        cwd=pathlib.Path(__file__).resolve().parents[1],
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": d})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == d
