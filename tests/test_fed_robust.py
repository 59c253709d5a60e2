"""Hostile-world federation tier (DESIGN.md §8): robust aggregators
(median / trimmed_mean / krum) must agree across engines, survive the
attacker harness that breaks plain fedavg, and compose with silo-dropout
schedules — plus unit pins for the masked statistics, the attack builders,
and the tiny-eps loss denominator fix."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import federated, privacy
from repro.core.federated import (AGGREGATORS, ROBUST_AGGREGATORS,
                                  apply_silo_scale, krum_select,
                                  make_dropout_schedule, masked_median,
                                  masked_trimmed_mean, robust_aggregate,
                                  robust_sync, run_federated)
from repro.models import mlp
from repro.optim import adamw


def _reg_loss(p, x, y):
    return mlp.mlp_per_example_loss(p, x, y, "regression")


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


def _max_abs_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# --------------------------------------------------------------------------
# masked statistics: unit pins against numpy
# --------------------------------------------------------------------------

def test_masked_median_matches_numpy():
    rng = np.random.default_rng(0)
    v = rng.standard_normal((6, 3, 2)).astype(np.float32)
    mask = np.array([1, 1, 0, 1, 1, 1], np.float32)
    got = np.asarray(masked_median(jnp.asarray(v), jnp.asarray(mask)))
    want = np.median(v[mask > 0], axis=0)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_masked_trimmed_mean_matches_numpy():
    rng = np.random.default_rng(1)
    v = rng.standard_normal((7, 5)).astype(np.float32)
    mask = np.array([1, 1, 1, 0, 1, 1, 0], np.float32)
    got = np.asarray(masked_trimmed_mean(jnp.asarray(v), jnp.asarray(mask),
                                         0.2))
    sub = np.sort(v[mask > 0], axis=0)           # k=5, trim floor(5*.2)=1
    want = sub[1:-1].mean(0)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_trimmed_mean_trim_clamped_to_survivor():
    """trim_frac large enough to trim everything must leave the middle
    value, not index out of range / divide by zero."""
    v = jnp.asarray([[1.0], [2.0], [100.0]])
    got = np.asarray(masked_trimmed_mean(v, jnp.ones((3,)), 0.49))
    np.testing.assert_allclose(got, [2.0], atol=1e-6)


def test_krum_selects_inside_honest_cluster():
    rng = np.random.default_rng(2)
    honest = rng.standard_normal((5, 8)).astype(np.float32) * 0.1
    outlier = np.full((1, 8), 50.0, np.float32)
    flat = jnp.asarray(np.concatenate([honest, outlier]))     # (d=6, P=8)
    idx = int(krum_select(flat, jnp.ones((6,)), 1))
    assert idx < 5                                           # never the outlier


def test_robust_aggregate_ignores_masked_outlier():
    """A masked-out silo must not move any robust statistic at all."""
    rng = np.random.default_rng(3)
    tree = {"w": rng.standard_normal((4, 3, 2)).astype(np.float32),
            "b": rng.standard_normal((4, 2)).astype(np.float32)}
    poisoned = jax.tree.map(lambda a: np.concatenate(
        [a, np.full((1,) + a.shape[1:], 1e6, np.float32)]), tree)
    m_clean = jnp.ones((4,))
    m_pois = jnp.asarray([1, 1, 1, 1, 0], jnp.float32)
    for agg in ROBUST_AGGREGATORS:
        clean = robust_aggregate(jax.tree.map(jnp.asarray, tree),
                                 m_clean, agg)
        masked = robust_aggregate(jax.tree.map(jnp.asarray, poisoned),
                                  m_pois, agg)
        assert _max_abs_diff(clean, masked) < 1e-6, agg


def test_apply_silo_scale_is_exact_noop_at_one():
    rng = np.random.default_rng(4)
    ref = {"w": rng.standard_normal((3, 2)).astype(np.float32)}
    sp = {"w": rng.standard_normal((5, 3, 2)).astype(np.float32)}
    out = apply_silo_scale(jax.tree.map(jnp.asarray, sp),
                           jax.tree.map(jnp.asarray, ref),
                           jnp.ones((5,)))
    assert np.array_equal(np.asarray(out["w"]), sp["w"])     # bit-exact


def test_robust_sync_broadcast_and_fallback():
    rng = np.random.default_rng(5)
    sp = {"w": jnp.asarray(rng.standard_normal((5, 4)).astype(np.float32))}
    out = robust_sync(sp, "median")
    # every silo restarts from the same point, and it is the median
    assert np.allclose(np.asarray(out["w"]),
                       np.median(np.asarray(sp["w"]), 0)[None])
    fb = robust_sync(sp, "fedavg")
    assert np.allclose(np.asarray(fb["w"]),
                       np.mean(np.asarray(sp["w"]), 0)[None], atol=1e-6)


# --------------------------------------------------------------------------
# dropout schedule + round weights
# --------------------------------------------------------------------------

def test_dropout_schedule_shape_and_liveness():
    av = make_dropout_schedule(0, rounds=50, num_silos=5, rate=0.5)
    assert av.shape == (50, 5) and av.dtype == np.float32
    assert set(np.unique(av)) <= {0.0, 1.0}
    assert np.all(av.sum(1) >= 1)          # no dead rounds, ever
    assert 0.2 < av.mean() < 0.8           # actually drops some silos


def test_dropout_schedule_empty_silos_never_available():
    sizes = np.array([10, 0, 7], np.float64)
    av = make_dropout_schedule(1, rounds=30, num_silos=3, rate=0.3,
                               sizes=sizes)
    assert np.all(av[:, 1] == 0.0)
    assert np.all(av.sum(1) >= 1)


def test_dropout_schedule_deterministic():
    a = make_dropout_schedule(7, 20, 4, 0.4)
    b = make_dropout_schedule(7, 20, 4, 0.4)
    assert np.array_equal(a, b)
    c = make_dropout_schedule(8, 20, 4, 0.4)
    assert not np.array_equal(a, c)


def test_round_weights_no_dropout_matches_norm_weights():
    sizes = np.array([40.0, 28.0, 52.0])
    wr = federated._round_weights(sizes, None, rounds=3)
    wn = federated._norm_weights(sizes)
    assert wr.shape == (3, 3)
    for r in range(3):
        assert np.array_equal(wr[r], wn)   # bit-identical, not just close


def test_round_weights_renormalize_over_present():
    sizes = np.array([10.0, 30.0, 60.0])
    av = np.array([[1, 0, 1], [1, 1, 1]], np.float32)
    wr = federated._round_weights(sizes, av, rounds=2)
    np.testing.assert_allclose(wr[0], [10 / 70, 0.0, 60 / 70], atol=1e-7)
    np.testing.assert_allclose(wr[1], [0.1, 0.3, 0.6], atol=1e-7)


# --------------------------------------------------------------------------
# attacker harness (core/privacy.py)
# --------------------------------------------------------------------------

def test_label_flip_silos_classification_and_regression():
    data = [(np.zeros((4, 2)), np.array([0, 1, 2, 2])),
            (np.zeros((3, 2)), np.array([[1.0], [-2.0], [3.0]]))]
    flipped = privacy.label_flip_silos(data, [0], num_classes=3)
    assert np.array_equal(flipped[0][1], [1, 2, 0, 0])
    assert flipped[1][1] is data[1][1]             # honest silo: no copy
    neg = privacy.label_flip_silos(data, [1])
    assert np.array_equal(neg[1][1], -data[1][1])


def test_grad_scale_vector_and_validation():
    v = privacy.grad_scale_vector(4, [1, 3], scale=-5.0)
    np.testing.assert_allclose(v, [1.0, -5.0, 1.0, -5.0])
    with pytest.raises(ValueError):
        privacy.grad_scale_vector(4, [4])


def test_apply_attack_routes():
    data = [(np.zeros((2, 2)), np.array([[1.0], [2.0]]))] * 3
    d, s = privacy.apply_attack(data, privacy.SiloAttack())
    assert s is None and len(d) == 3
    d, s = privacy.apply_attack(
        data, privacy.SiloAttack(corrupted=(1,), kind="grad_scale",
                                 scale=-3.0))
    assert np.array_equal(d[1][1], data[1][1])     # data untouched
    np.testing.assert_allclose(s, [1.0, -3.0, 1.0])
    d, s = privacy.apply_attack(
        data, privacy.SiloAttack(corrupted=(0,), kind="label_flip"))
    assert s is None and np.array_equal(d[0][1], -data[0][1])
    with pytest.raises(ValueError):
        privacy.SiloAttack(kind="what")


# --------------------------------------------------------------------------
# engine agreement: robust aggregators, dropout, attacks — host == scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("aggregator", list(ROBUST_AGGREGATORS))
def test_robust_scan_matches_host_ragged(aggregator):
    silos = _linear_silos([40, 28, 52, 33], seed=3)
    params = _params(seed=1)
    kw = dict(opt=adamw(1e-2), rounds=3, local_epochs=2, batch_size=16,
              aggregator=aggregator, seed=7, trim_frac=0.25, krum_f=1)
    host = run_federated(_reg_loss, params, silos, engine="host", **kw)
    scan = run_federated(_reg_loss, params, silos, engine="scan", **kw)
    assert _max_abs_diff(host.params, scan.params) < 1e-4
    for h, s in zip(host.history, scan.history):
        assert abs(h["loss"] - s["loss"]) < 1e-4 * max(1.0, abs(h["loss"]))


@pytest.mark.parametrize("aggregator", ["fedavg", "median"])
def test_dropout_scan_matches_host(aggregator):
    silos = _linear_silos([40, 28, 52], seed=5)
    params = _params(seed=2)
    kw = dict(opt=adamw(1e-2), rounds=4, local_epochs=2, batch_size=16,
              aggregator=aggregator, seed=11, dropout_rate=0.4)
    host = run_federated(_reg_loss, params, silos, engine="host", **kw)
    scan = run_federated(_reg_loss, params, silos, engine="scan", **kw)
    assert _max_abs_diff(host.params, scan.params) < 1e-4


def test_attacked_engines_agree_and_silo_scale_noop():
    """silo_scale threads identically through both engines, and an
    all-ones scale reproduces the unscaled run bit-for-bit."""
    silos = _linear_silos([32, 32, 32], seed=6)
    params = _params(seed=3)
    kw = dict(opt=adamw(1e-2), rounds=3, local_epochs=2, batch_size=16,
              aggregator="median", seed=13)
    scale = [1.0, -3.0, 1.0]
    host = run_federated(_reg_loss, params, silos, engine="host",
                         silo_scale=scale, **kw)
    scan = run_federated(_reg_loss, params, silos, engine="scan",
                         silo_scale=scale, **kw)
    assert _max_abs_diff(host.params, scan.params) < 1e-4
    plain = run_federated(_reg_loss, params, silos, engine="scan", **kw)
    ones = run_federated(_reg_loss, params, silos, engine="scan",
                         silo_scale=[1.0, 1.0, 1.0], **kw)
    assert _max_abs_diff(plain.params, ones.params) == 0.0


# --------------------------------------------------------------------------
# attack efficacy: robust converges where fedavg diverges
# --------------------------------------------------------------------------

def test_grad_scale_attack_breaks_fedavg_not_robust():
    silos = _linear_silos([48, 48, 48, 48, 48], seed=9)
    params = _params(seed=4)
    scale = privacy.grad_scale_vector(5, [2], scale=-5.0)
    kw = dict(opt=adamw(1e-2), rounds=8, local_epochs=2, batch_size=16,
              seed=17, engine="scan", silo_scale=scale)
    fedavg = run_federated(_reg_loss, params, silos, aggregator="fedavg",
                           **kw)
    med = run_federated(_reg_loss, params, silos, aggregator="median", **kw)
    clean = run_federated(_reg_loss, params, silos, aggregator="fedavg",
                          opt=adamw(1e-2), rounds=8, local_epochs=2,
                          batch_size=16, seed=17, engine="scan")
    bad = fedavg.history[-1]["loss"]
    good = med.history[-1]["loss"]
    ref = clean.history[-1]["loss"]
    assert good <= 0.5 * bad               # the ISSUE acceptance bound
    assert good <= 2.0 * ref + 0.1         # robust ~ clean, not merely < bad


def test_label_flip_attack_robust_beats_fedavg():
    """Data poisoning: judge the FINAL GLOBAL MODEL on honest data — the
    reported round loss averages in the corrupted silo's own (unfittable)
    objective, which masks the damage to everyone else. 64-row silos fill
    their 4 batches of 16, so every epoch is 4 full-batch steps."""
    silos = _linear_silos([64, 64, 64, 64, 64], seed=10)
    flipped = privacy.label_flip_silos(silos, [1])
    params = _params(seed=5)
    kw = dict(opt=adamw(1e-2), rounds=12, local_epochs=2, batch_size=16,
              seed=19, engine="scan")
    Xh = jnp.asarray(np.concatenate(
        [x for i, (x, _) in enumerate(silos) if i != 1]), jnp.float32)
    Yh = jnp.asarray(np.concatenate(
        [y for i, (_, y) in enumerate(silos) if i != 1]), jnp.float32)

    def honest_loss(p):
        return float(jnp.mean(_reg_loss(p, Xh, Yh)))

    fedavg = run_federated(_reg_loss, params, flipped, aggregator="fedavg",
                           **kw)
    tm = run_federated(_reg_loss, params, flipped,
                       aggregator="trimmed_mean", trim_frac=0.25, **kw)
    assert honest_loss(tm.params) <= 0.5 * honest_loss(fedavg.params)


# --------------------------------------------------------------------------
# tiny-eps denominator (satellite: the max(Σw, 1) deflation fix)
# --------------------------------------------------------------------------

def test_batch_loss_fractional_weights_not_deflated():
    """Pin the corrected denominator: with uniform fractional weights the
    masked batch loss must equal the plain mean — the old max(Σw, 1) clamp
    silently divided by 1 whenever the real weight mass was < 1."""
    params = _params(m=2, seed=6)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((2, 2)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((2, 1)).astype(np.float32))
    bl = federated._make_batch_loss(_reg_loss, True, 0.0)
    frac = float(bl(params, x, y, jnp.full((2,), 0.25), params))
    unit = float(bl(params, x, y, jnp.ones((2,)), params))
    # Σw = 0.5: old clamp would report frac == unit/2; fixed: equal means
    assert abs(frac - unit) < 1e-6 * max(1.0, abs(unit))
    assert frac > 0.0


def test_registry_contains_all_aggregators():
    assert set(ROBUST_AGGREGATORS) == {"median", "trimmed_mean", "krum"}
    assert set(AGGREGATORS) >= {"fedavg", "fedprox", "fedsgd"} | \
        set(ROBUST_AGGREGATORS)
    with pytest.raises(ValueError):
        run_federated(_reg_loss, _params(), _linear_silos([8]),
                      opt=adamw(1e-2), rounds=1, local_epochs=1,
                      batch_size=8, aggregator="fedfoo")


# --------------------------------------------------------------------------
# compiled-plan structure (repro.analysis): unsharded plans are
# collective-free and never bake tenant data into the executable
# --------------------------------------------------------------------------

@pytest.mark.parametrize("aggregator", ["fedavg", "median", "trimmed_mean",
                                        "krum"])
def test_unsharded_plan_collective_free_and_data_free(aggregator):
    """Without a mesh the whole plan is a single-device program: the
    collective census must be empty (any all-gather/all-reduce here would
    mean the robust boundary leaked shard_map machinery into the vmap
    path), and the lowered module must not embed the silo data."""
    from repro.analysis import assert_no_baked_data, collective_census
    from repro.core.federated import pad_silo_data

    silos = _linear_silos([24, 17, 20], m=8, seed=3)
    params = _params(m=8, seed=3)
    padded = pad_silo_data(silos, 8)
    bl = federated._make_batch_loss(_reg_loss, True, 0.0)
    plan = federated.make_fl_plan(
        num_silos=padded.num_silos, num_batches=padded.num_batches,
        batch_size=padded.batch_size, opt=adamw(1e-2), batch_loss=bl,
        rounds=2, local_epochs=2, aggregator=aggregator)
    lowered = plan.lower(params, *federated._plan_args(padded, 0, 2))
    assert collective_census(lowered) == {}
    assert_no_baked_data(lowered, min_elems=256)
