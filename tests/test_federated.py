"""Federated tier: host simulation semantics + mesh-level collective
structure (the paper's 'no iterative cross-silo traffic' made checkable)."""
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.federated import (fedavg_average, fedavg_sync, run_federated,
                                  silo_replicate)
from repro.models import mlp
from repro.optim import adamw, sgd

REPO = pathlib.Path(__file__).resolve().parents[1]


def _toy_data(n=64, m=4, silos=2, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((m, 1))
    X = rng.standard_normal((n, m))
    Y = X @ w + 0.01 * rng.standard_normal((n, 1))
    per = n // silos
    return [(X[i * per:(i + 1) * per], Y[i * per:(i + 1) * per])
            for i in range(silos)], (X, Y)


def test_fedavg_average_weighted():
    p1 = {"w": jnp.ones((2, 2))}
    p2 = {"w": jnp.zeros((2, 2))}
    avg = fedavg_average([p1, p2], [3, 1])
    np.testing.assert_allclose(np.asarray(avg["w"]), 0.75)


def test_fedavg_learns_linear_regression():
    silo_data, (X, Y) = _toy_data()
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), 4, (8,), 1)
    loss = lambda p, x, y: mlp.mlp_loss(p, x, y, "regression")
    res = run_federated(loss, params, silo_data, opt=adamw(1e-2), rounds=15,
                        local_epochs=2, batch_size=16)
    final = float(loss(res.params, jnp.asarray(X), jnp.asarray(Y)))
    assert final < 0.1, final


def test_fedprox_stays_closer_to_global():
    silo_data, _ = _toy_data(seed=3)
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), 4, (8,), 1)
    loss = lambda p, x, y: mlp.mlp_loss(p, x, y, "regression")
    res_avg = run_federated(loss, params, silo_data, opt=adamw(1e-2),
                            rounds=3, local_epochs=2)
    res_prox = run_federated(loss, params, silo_data, opt=adamw(1e-2),
                             rounds=3, local_epochs=2, aggregator="fedprox",
                             fedprox_mu=10.0)
    # strong proximal term keeps params nearer the start
    d_avg = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree_util.tree_leaves(res_avg.params),
        jax.tree_util.tree_leaves(params)))
    d_prox = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree_util.tree_leaves(res_prox.params),
        jax.tree_util.tree_leaves(params)))
    assert d_prox < d_avg


def test_fedsgd_runs():
    silo_data, (X, Y) = _toy_data()
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), 4, (8,), 1)
    loss = lambda p, x, y: mlp.mlp_loss(p, x, y, "regression")
    res = run_federated(loss, params, silo_data, opt=sgd(1e-1), rounds=50,
                        aggregator="fedsgd", local_epochs=1)
    final = float(loss(res.params, jnp.asarray(X), jnp.asarray(Y)))
    assert np.isfinite(final)


def test_silo_replicate_and_sync_roundtrip():
    params = {"w": jnp.arange(6.0).reshape(2, 3)}
    sp = silo_replicate(params, 4)
    assert sp["w"].shape == (4, 2, 3)
    # perturb silos differently, sync = mean
    sp = {"w": sp["w"] + jnp.arange(4.0)[:, None, None]}
    synced = fedavg_sync(sp)
    np.testing.assert_allclose(np.asarray(synced["w"][0]),
                               np.asarray(params["w"]) + 1.5)
    np.testing.assert_allclose(np.asarray(synced["w"][0]),
                               np.asarray(synced["w"][3]))


def test_weighted_sync():
    sp = {"w": jnp.stack([jnp.zeros((2,)), jnp.ones((2,))])}
    synced = fedavg_sync(sp, weights=jnp.asarray([1.0, 3.0]))
    np.testing.assert_allclose(np.asarray(synced["w"][0]), 0.75)


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.configs import REDUCED
    from repro.configs.base import TrainConfig, InputShape, FederatedConfig
    from repro.launch.specs import make_plan
    from repro.launch.roofline import iter_collectives
    cfg = REDUCED["llama3.2-1b"]
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = InputShape("t", seq_len=64, global_batch=8, kind="train")
    tc = TrainConfig(model=cfg, shape=shape, remat=False,
                     param_dtype="float32", compute_dtype="float32",
                     federated=FederatedConfig(num_silos=4, local_steps=4))

    def cross_silo(plan_mode):
        plan = make_plan(cfg, shape, mesh, mode=plan_mode, tc=tc)
        with mesh:
            c = jax.jit(plan.step_fn, in_shardings=plan.in_shardings,
                        out_shardings=plan.out_shardings
                        ).lower(*plan.args).compile()
        bad = 0
        # silo = data row; with (4,2) mesh, device // 2 = silo index
        for op, nbytes, groups in iter_collectives(c.as_text(), 8):
            for grp in groups:
                if len({d // 2 for d in grp}) > 1:
                    bad += 1
        return bad

    print("CLEAN" if cross_silo("feddcl") == 0 else "BAD")
    print("SYNC_CROSSES" if cross_silo("feddcl_sync") > 0 else "SYNC_LOCAL")
""")


@pytest.mark.slow
def test_no_cross_silo_collectives_in_local_step():
    """The lowered federated LOCAL step must contain no collective whose
    replica group spans silo boundaries; the SYNC step must contain one."""
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "CLEAN" in r.stdout, r.stdout
    assert "SYNC_CROSSES" in r.stdout, r.stdout
