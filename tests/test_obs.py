"""repro.obs: spans and counters that record only while the profiler
collects, at each layer boundary of the collaboration solve, onboarding
and the FL phase.

Each scenario runs once untraced and once under ``jax.profiler.trace``, at
a tiny size on the CPU: the traced run must record every span of its path
with the right count and nesting, the pinned number of host reads and the
padded layout's slot arithmetic, and must produce bitwise the outputs of
the untraced run, which records nothing.
"""
import glob
import threading
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import FedDCL
from repro.core import protocol
from repro.core.federated import PlanCache, bucket_pow2, run_federated
from repro.models import mlp
from repro.optim import adamw
from repro.serve_collab import ServeCollab

D, C, N, M = 3, 2, 40, 6           # groups, users per group, rows, raw width
KW = dict(m_tilde=4, anchor_r=60, seed=1, svd_backend="device")
SIZES, BATCH, ROUNDS, EPOCHS = (20, 13, 7), 8, 2, 1

FETCHES = "feddcl.device.fetches"
# span -> its parent span (None: a root) on each scenario's path
TREES = {
    "protocol": {
        "feddcl.protocol.run": None,
        "feddcl.protocol.step1": "feddcl.protocol.run",
        "feddcl.protocol.step2": "feddcl.protocol.run",
        "feddcl.protocol.step3": "feddcl.protocol.run",
        "feddcl.protocol.bases": "feddcl.protocol.step3",
        "feddcl.protocol.central": "feddcl.protocol.step3",
        "feddcl.protocol.solve_G": "feddcl.protocol.step3",
        "feddcl.protocol.apply_G": "feddcl.protocol.step3",
        "feddcl.protocol.onboard_state": "feddcl.protocol.run",
    },
    "onboard": {
        "feddcl.onboard.user": None,
        "feddcl.onboard.map": "feddcl.onboard.user",
        "feddcl.onboard.gram": "feddcl.onboard.user",
        "feddcl.onboard.factor": "feddcl.onboard.user",
        "feddcl.onboard.basis": "feddcl.onboard.user",
        "feddcl.onboard.central": "feddcl.onboard.user",
        "feddcl.onboard.solve_G": "feddcl.onboard.user",
        "feddcl.onboard.apply_G": "feddcl.onboard.user",
        "feddcl.serve.refresh_tables": None,
    },
    "fl": {"feddcl.fl.prepare": None, "feddcl.fl.plan": None},
}
# host reads of each path: D groups of equal width
PINNED_FETCHES = {
    # bases (U, s, V of one batched eigh), central (3), G, X̂, D Grams
    "protocol": 3 + 3 + 1 + 1 + D,
    # blocked Gram, basis (3), central (3), one G solve per group, X̂
    "onboard": 1 + 3 + 3 + D + 1,
    "fl": 1,                                         # the round losses
}


def _data(seed=0):
    rng = np.random.default_rng(seed)
    Xs = [[rng.standard_normal((N, M)) for _ in range(C)] for _ in range(D)]
    Ys = [[rng.integers(0, 3, N) for _ in range(C)] for _ in range(D)]
    return Xs, Ys, rng.standard_normal((N, M)), rng.integers(0, 3, N)


def _setup_outputs(setup):
    return ([setup.Z] + [g for row in setup.Gs for g in row]
            + list(setup.collab_X))


def _prepare(kind):
    """What the scenario starts from, built untraced."""
    Xs, Ys, Xn, Yn = _data()
    if kind == "protocol":
        return Xs, Ys
    setup = protocol.run_protocol(Xs, Ys, onboard=True, **KW)
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), setup.m_hat, (8,), 3)
    if kind == "onboard":
        return ServeCollab.from_setup(setup, params), Xn, Yn
    rng = np.random.default_rng(5)
    silos = [(rng.standard_normal((n, KW["m_tilde"])).astype(np.float32),
              rng.integers(0, 3, n)) for n in SIZES]
    return params, silos, PlanCache()


def _run(kind, state):
    """The scenario's traced part; returns its outputs."""
    if kind == "protocol":
        return _setup_outputs(protocol.run_protocol(*state, onboard=True,
                                                    **KW))
    if kind == "onboard":
        srv, Xn, Yn = state
        srv.onboard_user(1, Xn, Yn)
        return (_setup_outputs(srv.setup)
                + [np.asarray(a) for t in srv.tables for a in (t.M, t.mu)])
    params, silos, cache = state
    res = run_federated(
        lambda p, x, y: mlp.mlp_per_example_loss(p, x, y,
                                                 task="classification"),
        params, silos, opt=adamw(1e-2), rounds=ROUNDS, local_epochs=EPOCHS,
        batch_size=BATCH, engine="scan", cache=cache, seed=3)
    return jax.tree.leaves(res.params) + [np.array([h["loss"]
                                                    for h in res.history])]


@pytest.fixture(scope="module", params=sorted(TREES))
def scenario(request, tmp_path_factory):
    """(kind, untraced outputs, traced outputs, table, trace directory)."""
    kind = request.param
    obs.clear()
    plain = _run(kind, _prepare(kind))
    assert obs.snapshot() == {"spans": {}, "counters": {}}
    state = _prepare(kind)
    trace_dir = tmp_path_factory.mktemp(f"trace_{kind}")
    with jax.profiler.trace(str(trace_dir)):
        traced = _run(kind, state)
    table = obs.snapshot()
    obs.clear()
    return kind, plain, traced, table, trace_dir


def test_off_records_nothing_and_allocates_nothing():
    obs.clear()
    assert obs.span("a") is obs.span("b")
    with obs.span("feddcl.test.off"):
        obs.count("feddcl.test.off")
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def test_off_full_pipeline_records_nothing():
    """fit -> serve -> onboard_user with the profiler off leaves the table
    empty."""
    Xs, Ys, Xn, Yn = _data(1)
    obs.clear()
    model = FedDCL(m_tilde=4, anchor_r=60, hidden=(8,), task="classification",
                   rounds=2, local_epochs=1, batch_size=8,
                   svd_backend="device")
    model.fit(Xs, Ys)
    srv = model.serve()
    srv.submit(Xs[0][0][:5], 0, 0)
    srv.serve()
    srv.onboard_user(0, Xn, Yn)
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def test_self_time_is_total_less_children_on_the_same_thread(tmp_path):
    obs.clear()

    def other_thread():
        with obs.span("feddcl.test.other"):
            time.sleep(0.02)

    with jax.profiler.trace(str(tmp_path)):
        with obs.span("feddcl.test.outer"):
            t = threading.Thread(target=other_thread)
            t.start()
            for _ in range(2):
                with obs.span("feddcl.test.inner"):
                    time.sleep(0.01)
            t.join(timeout=10)
            obs.count("feddcl.test.counter", 3)
    assert not t.is_alive()
    sp = obs.snapshot()["spans"]
    outer, inner = sp["feddcl.test.outer"], sp["feddcl.test.inner"]
    assert (outer["n"], inner["n"], sp["feddcl.test.other"]["n"]) == (1, 2, 1)
    assert inner["total_s"] >= 0.02 and inner["self_s"] == inner["total_s"]
    assert inner["max_s"] <= inner["total_s"] <= 2 * inner["max_s"]
    # the other thread's span runs inside outer's interval but is not its
    # child
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-9)
    assert obs.snapshot()["counters"] == {"feddcl.test.counter": 3}
    obs.clear()
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def test_traced_run_records_every_span_of_its_path(scenario):
    kind, _, _, table, _ = scenario
    tree, spans = TREES[kind], table["spans"]
    assert set(spans) == set(tree)
    for name, rec in spans.items():
        assert rec["n"] == 1, name
        assert 0 <= rec["self_s"] <= rec["total_s"] == rec["max_s"], name
    for name, rec in spans.items():
        kids = [k for k, parent in tree.items() if parent == name]
        child_s = sum(spans[k]["total_s"] for k in kids)
        assert child_s <= rec["total_s"], name
        assert rec["self_s"] == pytest.approx(rec["total_s"] - child_s,
                                              abs=1e-9), name


def test_traced_run_counts_fetches_and_slots(scenario):
    kind, _, _, table, _ = scenario
    want = {FETCHES: PINNED_FETCHES[kind]}
    if kind == "fl":
        steps = EPOCHS * ROUNDS
        slots = bucket_pow2(len(SIZES)) * BATCH * bucket_pow2(
            -(-max(SIZES) // BATCH))
        want["feddcl.fl.sample_steps"] = sum(SIZES) * steps
        want["feddcl.fl.slot_steps"] = slots * steps
    assert table["counters"] == want


def test_traced_outputs_equal_untraced_bitwise(scenario):
    kind, plain, traced, _, _ = scenario
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spans_land_on_a_host_plane_of_the_xplane(scenario):
    from jax.profiler import ProfileData
    kind, _, _, table, trace_dir = scenario
    files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    seen = set()
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            seen |= {ev.name for line in plane.lines for ev in line.events
                     if ev.name.startswith("feddcl.")}
    assert seen == set(table["spans"])
