"""FedEngine tier: the compiled scan engine must be a drop-in replacement
for the paper-faithful host loop — same seed, same schedule, same results —
and the padding masks must provably keep zero-sample slots out of every
average (DESIGN.md §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _prop import given, settings, st

from repro.core import federated
from repro.core.baselines import sgd_train
from repro.core.federated import pad_silo_data, run_federated
from repro.data.partition import split_dirichlet, split_iid
from repro.data.tabular import make_dataset, train_test_split
from repro.models import mlp
from repro.optim import adamw, sgd


def _reg_loss(p, x, y):
    return mlp.mlp_per_example_loss(p, x, y, "regression")


def _cls_loss(p, x, y):
    return mlp.mlp_per_example_loss(p, x, y, "classification")


def _linear_silos(sizes, m=4, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((m, 1))
    out = []
    for k, n in enumerate(sizes):
        r = np.random.default_rng(seed * 97 + k + 1)
        X = r.standard_normal((n, m))
        out.append((X, X @ w + 0.01 * r.standard_normal((n, 1))))
    return out


def _params(m=4, out=1, seed=0):
    return mlp.init_mlp_params(jax.random.PRNGKey(seed), m, (8,), out)


def _max_rel_diff(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))) /
              (np.max(np.abs(np.asarray(x))) + 1e-12))
        for x, y in zip(la, lb))


# --------------------------------------------------------------------------
# host == scan: every aggregator, ragged silos included
# --------------------------------------------------------------------------

@pytest.mark.parametrize("aggregator", ["fedavg", "fedprox", "fedsgd"])
@pytest.mark.parametrize("sizes", [(32, 32), (40, 28, 52)],
                         ids=["equal", "ragged"])
def test_scan_matches_host_params_and_trajectory(aggregator, sizes):
    silos = _linear_silos(list(sizes), seed=3)
    params = _params(seed=1)
    kw = dict(opt=adamw(1e-2), rounds=4, local_epochs=2, batch_size=16,
              aggregator=aggregator,
              fedprox_mu=0.1 if aggregator == "fedprox" else 0.0, seed=7)
    host = run_federated(_reg_loss, params, silos, engine="host", **kw)
    scan = run_federated(_reg_loss, params, silos, engine="scan", **kw)
    assert _max_rel_diff(host.params, scan.params) < 1e-4
    for h, s in zip(host.history, scan.history):
        assert abs(h["loss"] - s["loss"]) < 1e-4 * max(1.0, abs(h["loss"]))


@pytest.mark.parametrize("split", ["iid", "dirichlet"])
def test_scan_matches_host_on_paper_partitions(split):
    """Exp-I-shaped data (classification, Dirichlet non-IID included):
    engines agree on the real protocol inputs, not just toy regressions."""
    ds = make_dataset("human_activity", n=2200, seed=0)
    (Xtr, Ytr), _ = train_test_split(ds, 800, 400, seed=0)
    if split == "iid":
        Xs, Ys = split_iid(Xtr, Ytr, d=2, c=[2, 2], n_ij=100, seed=0)
    else:
        Xs, Ys = split_dirichlet(Xtr, Ytr, d=2, c=[2, 2], n_ij=100,
                                 alpha=0.3, seed=0)
    silos = [(Xs[i][j], Ys[i][j]) for i in range(2) for j in range(2)]
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), Xtr.shape[1], (16,), 5)
    kw = dict(opt=adamw(1e-3), rounds=3, local_epochs=2, batch_size=32, seed=0)
    host = run_federated(_cls_loss, params, silos, engine="host", **kw)
    scan = run_federated(_cls_loss, params, silos, engine="scan", **kw)
    assert _max_rel_diff(host.params, scan.params) < 1e-4


def test_scan_matches_host_with_eval_and_sgd_train():
    """The d=1 degenerate case (sgd_train) and the eval_fn carry path."""
    X, Y = _linear_silos([100], seed=5)[0]
    params = _params(seed=2)
    ev = lambda p: {"metric": float(jnp.mean(jnp.abs(
        jax.tree_util.tree_leaves(p)[0])))}
    ph, hh = sgd_train(_reg_loss, params, X, Y, opt=adamw(1e-2), epochs=3,
                       eval_fn=ev, engine="host")
    ps, hs = sgd_train(_reg_loss, params, X, Y, opt=adamw(1e-2), epochs=3,
                       eval_fn=ev, engine="scan")
    assert _max_rel_diff(ph, ps) < 1e-4
    assert len(hh) == len(hs) == 3
    for a, b in zip(hh, hs):
        assert a["epoch"] == b["epoch"]
        assert abs(a["metric"] - b["metric"]) < 1e-5


@pytest.mark.parametrize("eval_chunk", [4, 8],
                         ids=["two-dispatches", "one-dispatch"])
def test_streamed_eval_history_matches_stacked(eval_chunk):
    """Regression for the bounded-memory eval path: the chunked streamed
    history must match (a) the legacy collect="stack" plan that
    materialized a (rounds, |params|) stack on device, and (b) the host
    engine — both per-round losses and eval_fn outputs. eval_chunk=4 with
    rounds=6 exercises the ragged final dispatch (4 + 2)."""
    silos = _linear_silos([40, 28, 52], seed=3)
    params = _params(seed=1)
    ev = lambda p: {"w0": float(np.asarray(
        jax.tree_util.tree_leaves(p)[0]).ravel()[0])}
    kw = dict(opt=adamw(1e-2), rounds=6, local_epochs=2, batch_size=16,
              seed=7)
    host = run_federated(_reg_loss, params, silos, engine="host", eval_fn=ev,
                         **kw)
    scan = run_federated(_reg_loss, params, silos, engine="scan", eval_fn=ev,
                         eval_chunk=eval_chunk, **kw)
    # the OLD stacked path, driven through the same runner
    padded = pad_silo_data(silos, 16)
    batch_loss = federated._make_batch_loss(_reg_loss, True, 0.0)
    stacked_plan = federated.make_fl_plan(
        num_silos=padded.num_silos, num_batches=padded.num_batches,
        batch_size=padded.batch_size, opt=adamw(1e-2), batch_loss=batch_loss,
        rounds=6, local_epochs=2, collect="stack", masked=padded.has_padding)
    legacy = federated._run_scan(
        stacked_plan, params, federated._plan_args(padded, 7, 6), rounds=6,
        eval_fn=ev)
    assert len(scan.history) == len(legacy.history) == 6
    for s, l, h in zip(scan.history, legacy.history, host.history):
        assert abs(s["w0"] - l["w0"]) < 1e-6
        assert abs(s["loss"] - l["loss"]) < 1e-6 * max(1.0, abs(l["loss"]))
        assert abs(s["w0"] - h["w0"]) < 1e-4
    assert _max_rel_diff(scan.params, legacy.params) < 1e-6
    assert _max_rel_diff(scan.params, host.params) < 1e-4


def test_momentum_optimizer_state_vmaps_through_scan():
    silos = _linear_silos([24, 24], seed=9)
    params = _params(seed=3)
    kw = dict(opt=sgd(1e-2, momentum=0.9), rounds=3, local_epochs=2,
              batch_size=8, seed=1)
    host = run_federated(_reg_loss, params, silos, engine="host", **kw)
    scan = run_federated(_reg_loss, params, silos, engine="scan", **kw)
    assert _max_rel_diff(host.params, scan.params) < 1e-4


# --------------------------------------------------------------------------
# loss reporting: sample-weighted mean of per-silo final-epoch losses
# --------------------------------------------------------------------------

def test_round_loss_is_sample_weighted_over_silos():
    """Regression for the old bug (last minibatch of the LAST silo only):
    duplicating a silo's data must not change the reported round loss, and
    the loss must weight silos by sample count."""
    silos = _linear_silos([32, 64], seed=11)
    params = _params(seed=4)
    kw = dict(opt=adamw(1e-3), rounds=1, local_epochs=1, batch_size=16, seed=0)
    res = run_federated(_reg_loss, params, silos, engine="host", **kw)
    # recompute by hand from the engine's own schedule
    padded = pad_silo_data(silos, 16)
    perms = np.asarray(federated.round_perms(
        jax.random.PRNGKey(0), 0, 2, 1, padded.n_slots))
    num = den = 0.0
    opt = adamw(1e-3)
    for i in range(2):
        p, o = params, opt.init(params)
        s_num = s_den = 0.0
        for b in perms[i, 0].reshape(-1, 16):
            x, y, w = (jnp.asarray(padded.X[i][b]), jnp.asarray(padded.Y[i][b]),
                       jnp.asarray(padded.w[i][b]))
            l = _reg_loss(p, x, y)
            bl = float(jnp.sum(w * l) / jnp.maximum(jnp.sum(w), 1.0))
            grads = jax.grad(lambda pp: jnp.sum(w * _reg_loss(pp, x, y)) /
                             jnp.maximum(jnp.sum(w), 1.0))(p)
            upd, o = opt.update(grads, o, p)
            p = jax.tree.map(lambda a, u: a + u, p, upd)
            s_num += bl * float(w.sum())
            s_den += float(w.sum())
        num += padded.sizes[i] * (s_num / s_den)
        den += padded.sizes[i]
    assert abs(res.history[0]["loss"] - num / den) < 1e-5


# --------------------------------------------------------------------------
# padding property: masks never leak zero-sample gradients
# --------------------------------------------------------------------------

@settings(deadline=None, max_examples=6)
@given(n1=st.integers(5, 40), n2=st.integers(5, 40),
       fill=st.sampled_from([123.0, -999.0, 1e4]))
def test_padding_fill_never_leaks_into_training(n1, n2, fill):
    """Whatever garbage sits in padded X slots, masked losses/grads must be
    bit-identical to zero-fill — i.e. padding contributes exactly nothing."""
    silos = _linear_silos([n1, n2], seed=n1 * 100 + n2)
    params = _params(seed=5)
    kw = dict(opt=adamw(1e-2), rounds=2, local_epochs=2, batch_size=16, seed=2)
    for engine in ("host", "scan"):
        clean = run_federated(_reg_loss, params, silos, engine=engine,
                              pad_fill=0.0, **kw)
        dirty = run_federated(_reg_loss, params, silos, engine=engine,
                              pad_fill=fill, **kw)
        for a, b in zip(jax.tree_util.tree_leaves(clean.params),
                        jax.tree_util.tree_leaves(dirty.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for h, g in zip(clean.history, dirty.history):
            assert h["loss"] == g["loss"]


def test_all_padding_batch_is_exact_noop():
    """A batch with ZERO real samples must leave params AND optimizer state
    untouched — without the masked-step guard Adam would still advance its
    step counter, decay momentum, and coast parameters, giving small ragged
    silos extra effective steps (DESIGN.md §4 rule 2)."""
    params = _params(seed=7)
    opt = adamw(1e-2)
    batch_loss = federated._make_batch_loss(_reg_loss, True, 0.0)
    step = federated._make_sgd_step(batch_loss, opt, masked=True)
    x = jnp.full((8, 4), 1e3)                            # garbage padding
    y = jnp.zeros((8, 1))
    w0 = jnp.zeros((8,))
    # warm the optimizer state so momentum could coast if unguarded
    state = opt.init(params)
    p1, s1, _ = step(params, state, jnp.ones((8, 4)), y, jnp.ones((8,)),
                     params)
    p2, s2, loss = step(p1, s1, x, y, w0, params)
    assert float(loss) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(s1),
                    jax.tree_util.tree_leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tiny_silo_takes_only_real_steps():
    """Silo with 1 real sample in 64 slots: its local training for one
    epoch is exactly ONE optimizer step on that sample, wherever the
    permutation lands it — engines agree and match the manual step."""
    silos = _linear_silos([1, 64], seed=13)
    params = _params(seed=8)
    kw = dict(opt=adamw(1e-2), rounds=1, local_epochs=1, batch_size=16,
              seed=4)
    host = run_federated(_reg_loss, params, silos, engine="host", **kw)
    scan = run_federated(_reg_loss, params, silos, engine="scan", **kw)
    assert _max_rel_diff(host.params, scan.params) < 1e-5
    # manual: silo-0 local params after one adam step on its single sample
    opt = adamw(1e-2)
    x, y = jnp.asarray(silos[0][0]), jnp.asarray(silos[0][1])
    grads = jax.grad(lambda p: jnp.mean(_reg_loss(p, x, y)))(params)
    upd, _ = opt.update(grads, opt.init(params), params)
    p0 = jax.tree.map(lambda a, u: a + u, params, upd)
    # recover silo-0 locals from the weighted mean: gp = (1*p0 + 64*p1)/65
    # → check gp is consistent with the manual p0 given engine-trained p1
    # (equivalently: train silo 0 alone and compare)
    solo = run_federated(_reg_loss, params, silos[:1], engine="host", **kw)
    assert _max_rel_diff(solo.params, p0) < 1e-5


def test_fedsgd_weighted_average_excludes_padding():
    """FedSGD full-batch gradients are masked means: a silo padded from 10
    to 40 slots must contribute the gradient of its 10 real samples only."""
    silos = _linear_silos([10, 40], seed=21)
    params = _params(seed=6)
    kw = dict(opt=sgd(1e-1), rounds=1, local_epochs=1, aggregator="fedsgd",
              seed=0)
    res = run_federated(_reg_loss, params, silos, engine="scan", **kw)
    # manual: per-silo mean grads on REAL rows, sample-weighted 10:40
    def silo_grad(X, Y):
        return jax.grad(lambda p: jnp.mean(_reg_loss(p, jnp.asarray(X),
                                                     jnp.asarray(Y))))(params)
    g = jax.tree.map(lambda a, b: (10 * a + 40 * b) / 50.0,
                     silo_grad(*silos[0]), silo_grad(*silos[1]))
    manual = jax.tree.map(lambda p, gg: p - 1e-1 * gg, params, g)
    assert _max_rel_diff(res.params, manual) < 1e-5


# --------------------------------------------------------------------------
# guard rails
# --------------------------------------------------------------------------

def test_scalar_loss_with_padding_raises():
    silos = _linear_silos([20, 30], seed=1)
    scalar = lambda p, x, y: mlp.mlp_loss(p, x, y, "regression")
    with pytest.raises(ValueError, match="per-example"):
        run_federated(scalar, _params(), silos, opt=adamw(1e-2), rounds=1,
                      local_epochs=1, batch_size=16)


def test_unknown_engine_and_aggregator_raise():
    silos = _linear_silos([16], seed=1)
    with pytest.raises(ValueError, match="engine"):
        run_federated(_reg_loss, _params(), silos, opt=adamw(1e-2), rounds=1,
                      local_epochs=1, engine="warp")
    with pytest.raises(ValueError, match="aggregator"):
        run_federated(_reg_loss, _params(), silos, opt=adamw(1e-2), rounds=1,
                      local_epochs=1, aggregator="fedfoo")
