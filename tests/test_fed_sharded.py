"""Sharded-engine tier (DESIGN.md §7): the mesh-sharded FL plan must be a
drop-in for the single-device vmap plan — same schedule, same results —
with round-boundary psums as the ONLY collectives.

The in-process tests build a mesh over however many devices exist (1 on a
plain tier-1 run — plumbing only; 8 on the CI matrix leg that exports
XLA_FLAGS=--xla_force_host_platform_device_count=8 — real sharding). The
subprocess tests force 8 virtual devices regardless, so ragged / non
divisible silo counts and the collective-structure invariant are proven on
every run.
"""
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import federated
from repro.core.federated import (default_silo_axes, num_silo_shards,
                                  run_federated)
from repro.launch.mesh import make_host_mesh
from repro.models import mlp
from repro.optim import adamw, sgd

DEV = jax.device_count()
REPO = pathlib.Path(__file__).resolve().parents[1]


def _linear_silos(sizes, m=4, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((m, 1))
    out = []
    for k, n in enumerate(sizes):
        r = np.random.default_rng(seed * 97 + k + 1)
        X = r.standard_normal((n, m))
        out.append((X, X @ w + 0.01 * r.standard_normal((n, 1))))
    return out


def _params(seed=0):
    return mlp.init_mlp_params(jax.random.PRNGKey(seed), 4, (8,), 1)


def _reg_loss(p, x, y):
    return mlp.mlp_per_example_loss(p, x, y, "regression")


def _max_rel_diff(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))) /
              (np.max(np.abs(np.asarray(x))) + 1e-12))
        for x, y in zip(la, lb))


KW = dict(opt=adamw(1e-2), rounds=3, local_epochs=2, batch_size=16,
          engine="scan", seed=7)


# --------------------------------------------------------------------------
# sharded == unsharded, in-process (real sharding on the 8-device CI leg)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("aggregator", ["fedavg", "fedprox", "fedsgd"])
def test_sharded_matches_unsharded_all_aggregators(aggregator):
    """Ragged silo count (d=3 — not divisible by any multi-device mesh):
    run_federated pads d up to the shard multiple with exact-no-op empty
    silos, so the sharded result matches the vmap plan ≤1e-5."""
    silos = _linear_silos([20, 13, 17], seed=3)
    params = _params(seed=1)
    kw = {**KW, "aggregator": aggregator,
          "fedprox_mu": 0.1 if aggregator == "fedprox" else 0.0}
    base = run_federated(_reg_loss, params, silos, **kw)
    sh = run_federated(_reg_loss, params, silos, mesh=make_host_mesh(model=1),
                       **kw)
    assert _max_rel_diff(base.params, sh.params) <= 1e-5
    for a, b in zip(base.history, sh.history):
        assert abs(a["loss"] - b["loss"]) <= 1e-5 * max(1.0, abs(a["loss"]))


def test_sharded_streamed_eval_matches_unsharded():
    """mesh= composes with eval_fn: the chunked streamed-eval path runs
    inside the shard_map and the per-round history still matches."""
    silos = _linear_silos([20, 13, 17], seed=5)
    params = _params(seed=2)
    ev = lambda p: {"w0": float(jnp.mean(jnp.abs(
        jax.tree_util.tree_leaves(p)[0])))}
    base = run_federated(_reg_loss, params, silos, eval_fn=ev, **KW)
    sh = run_federated(_reg_loss, params, silos, eval_fn=ev,
                       mesh=make_host_mesh(model=1), eval_chunk=2, **KW)
    assert len(sh.history) == KW["rounds"]
    for a, b in zip(base.history, sh.history):
        assert abs(a["w0"] - b["w0"]) <= 1e-5


def test_sharded_carries_opt_state_across_rounds():
    silos = _linear_silos([18, 25], seed=9)
    params = _params(seed=3)
    kw = {**KW, "opt": sgd(1e-2, momentum=0.9),
          "reset_opt_per_round": False}
    base = run_federated(_reg_loss, params, silos, **kw)
    sh = run_federated(_reg_loss, params, silos, mesh=make_host_mesh(model=1),
                       **kw)
    assert _max_rel_diff(base.params, sh.params) <= 1e-5


def test_mesh_requires_scan_engine():
    silos = _linear_silos([16], seed=1)
    with pytest.raises(ValueError, match="scan"):
        run_federated(_reg_loss, _params(), silos, opt=adamw(1e-2), rounds=1,
                      local_epochs=1, engine="host",
                      mesh=make_host_mesh(model=1))


def test_num_silo_shards_validates_axes():
    mesh = make_host_mesh(model=1)
    assert num_silo_shards(mesh) == mesh.devices.shape[0]
    assert default_silo_axes(mesh) == ("data",)
    with pytest.raises(ValueError, match="nope"):
        num_silo_shards(mesh, ("nope",))


@pytest.mark.skipif(DEV < 8, reason="needs 8 devices (CI sharded leg)")
def test_hierarchical_pod_data_mesh_matches_unsharded():
    """(2, 2, 2) pod/data/model mesh: the silo dim spans ("pod", "data")
    jointly (4 shards), aggregation is the two-level psum — intra-pod
    first, cross-pod second — and results still match the vmap plan."""
    devices = np.asarray(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = jax.sharding.Mesh(devices, ("pod", "data", "model"))
    assert default_silo_axes(mesh) == ("pod", "data")
    assert num_silo_shards(mesh) == 4
    silos = _linear_silos([20, 13, 17], seed=3)
    params = _params(seed=1)
    base = run_federated(_reg_loss, params, silos, **KW)
    sh = run_federated(_reg_loss, params, silos, mesh=mesh, **KW)
    assert _max_rel_diff(base.params, sh.params) <= 1e-5


# --------------------------------------------------------------------------
# forced 8-virtual-device subprocess: ragged d, all aggregators, collective
# structure — proven even when the parent pytest runs on 1 device
# --------------------------------------------------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis import assert_no_baked_data, collective_census
    from repro.core import federated
    from repro.core.federated import pad_silo_data, run_federated
    from repro.launch.mesh import make_host_mesh
    from repro.models import mlp
    from repro.optim import adamw

    assert jax.device_count() == 8

    def loss(p, x, y):
        return mlp.mlp_per_example_loss(p, x, y, "regression")

    rng = np.random.default_rng(0)
    w = rng.standard_normal((4, 1))
    silos = []
    for n in (12, 20, 9, 15, 11):           # d=5: ragged AND not divisible
        X = rng.standard_normal((n, 4))
        silos.append((X, X @ w + 0.01 * rng.standard_normal((n, 1))))
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), 4, (8,), 1)
    mesh = make_host_mesh(model=1)          # (8, 1) -> 8 silo shards

    def flat(r):
        return np.concatenate([np.ravel(np.asarray(l))
                               for l in jax.tree.leaves(r.params)])

    for agg in ("fedavg", "fedprox", "fedsgd",
                "median", "trimmed_mean", "krum"):
        kw = dict(opt=adamw(1e-2), rounds=2, local_epochs=2, batch_size=8,
                  engine="scan", seed=3, aggregator=agg,
                  fedprox_mu=0.1 if agg == "fedprox" else 0.0)
        if agg in federated.ROBUST_AGGREGATORS:
            # hostile extras ride along: dropout + one scaled silo — the
            # robust sharded boundary must still match the vmap plan
            kw.update(dropout_rate=0.25,
                      silo_scale=[1.0, -3.0, 1.0, 1.0, 1.0])
        base = run_federated(loss, params, silos, **kw)
        sh = run_federated(loss, params, silos, mesh=mesh, **kw)
        rel = np.max(np.abs(flat(base) - flat(sh))) / (
            np.max(np.abs(flat(base))) + 1e-12)
        assert rel <= 1e-5, (agg, rel)
        print("AGREE", agg, rel)

    # collective structure: lower the sharded plan and census collectives
    # via repro.analysis (same regex the old inline counter used, so the
    # asserted counts are bit-identical). The rounds-scan body must hold
    # exactly one all-reduce per param leaf plus one for the loss, per
    # hierarchy level — and the count must not change with local_epochs (a
    # leak of collectives into the local phase would scale with E).
    batch_loss = federated._make_batch_loss(loss, True, 0.0)
    padded = pad_silo_data(silos, 8, shards=8)
    args = federated._plan_args(padded, 3, 2)

    def hist(epochs, aggregator):
        plan = federated.make_fl_plan(
            num_silos=padded.num_silos, num_batches=padded.num_batches,
            batch_size=padded.batch_size, opt=adamw(1e-2),
            batch_loss=batch_loss, rounds=2, local_epochs=epochs,
            aggregator=aggregator, mesh=mesh)
        lowered = plan.lower(params, *args)
        # piggyback the privacy audit: no plan flavor may bake tenant data
        assert_no_baked_data(lowered, min_elems=512)
        return collective_census(lowered)

    leaves = len(jax.tree_util.tree_leaves(params))
    # weighted boundary: one all-reduce per leaf + one for the loss, no
    # other collective, invariant to local_epochs (a leak into the local
    # phase would scale with E)
    h1, h3 = hist(1, "fedavg"), hist(3, "fedavg")
    assert h1 == h3 == {"all-reduce": leaves + 1}, (h1, h3, leaves)
    # robust boundary: the psum becomes one all-gather per leaf plus one
    # for the availability mask; the loss all-reduce is the only reduce
    for agg in ("median", "trimmed_mean", "krum"):
        hr = hist(2, agg)
        assert hr == {"all-reduce": 1, "all-gather": leaves + 1}, (agg, hr)
    print("COLLECTIVES_OK", h1["all-reduce"])
""")


def test_sharded_8dev_agreement_and_collective_structure():
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    for agg in ("fedavg", "fedprox", "fedsgd",
                "median", "trimmed_mean", "krum"):
        assert f"AGREE {agg}" in r.stdout, r.stdout
    assert "COLLECTIVES_OK" in r.stdout, r.stdout


MESH_VALIDATION_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    import jax
    from repro.launch.mesh import make_host_mesh

    assert jax.device_count() == 6
    m = make_host_mesh(model=2)             # data defaults to 6 // 2 = 3
    assert m.devices.shape == (3, 2), m.devices.shape
    try:
        make_host_mesh(model=4)             # 6 // 4 = 1 -> 1x4 over 6: valid
    except ValueError:
        raise SystemExit("model=4 with data=1 should fit on 6 devices")
    try:
        make_host_mesh(model=4, data=2)     # 8 > 6 devices
        raise SystemExit("data=2 model=4 should have raised")
    except ValueError as e:
        assert "6" in str(e) and "8" in str(e), e
        print("RAISES_WITH_COUNT")
    try:
        make_host_mesh(model=7)             # more model shards than devices
        raise SystemExit("model=7 should have raised")
    except ValueError as e:
        assert "6" in str(e), e
        print("MODEL_TOO_BIG_OK")
""")


def test_make_host_mesh_validation_names_device_count():
    """Satellite: the old `data * model <= n` assert admitted shapes that
    only failed later inside mesh consumers; now invalid shapes raise
    immediately, naming the available device count."""
    r = subprocess.run([sys.executable, "-c", MESH_VALIDATION_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:] or r.stdout
    assert "RAISES_WITH_COUNT" in r.stdout, r.stdout
    assert "MODEL_TOO_BIG_OK" in r.stdout, r.stdout


# --------------------------------------------------------------------------
# bounded-memory eval: rounds ≫ eval_chunk streams, never stacks
# --------------------------------------------------------------------------

def test_rounds_200_streamed_eval_smoke():
    """A rounds=200 run with eval enabled — the config class the old
    (rounds, |params|) stack made impossible — completes in chunked
    dispatches and reports one history record per round."""
    silos = _linear_silos([12, 10], seed=4)
    params = _params(seed=5)
    calls = []
    ev = lambda p: {"w0": float(np.asarray(
        jax.tree_util.tree_leaves(p)[0]).ravel()[0])}
    res = run_federated(_reg_loss, params, silos, opt=adamw(1e-2), rounds=200,
                        local_epochs=1, batch_size=8, engine="scan", seed=6,
                        eval_fn=lambda p: (calls.append(1), ev(p))[1],
                        eval_chunk=16)
    assert len(res.history) == 200 and len(calls) == 200
    assert all(np.isfinite(h["loss"]) and np.isfinite(h["w0"])
               for h in res.history)
    # params evolve across the stream (the carry really advances)
    assert res.history[0]["w0"] != res.history[-1]["w0"]
