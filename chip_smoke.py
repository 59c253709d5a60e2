"""On-chip smoke of the FedDCL main path, through the user-facing entry points.

One process, one TPU chip, the paper's widest network (the MNIST MLP of
configs/feddcl_mlp.py: m=784 raw features, m̃=m̂=50, hidden (500, 100), 10
classes) on the paper's layout (d=5 groups × c=4 users × 1000 rows,
anchor r=2000), data generated from a seed:

  fit      FedDCL.fit with svd_backend="device" (Pallas Gram kernel, eigh,
           batched QR) and engine="scan" (the compiled, cached FL plan)
  serve    a mixed-tenant request batch through FedDCL.serve()
  onboard  one new tenant onboarded onto the live server, then served

Every phase is checked against the repo's own reference at the repo's own
bar: step 3 against the NumPy-f64 host backend (≤1e-3 relative Frobenius),
the scan plan against the host FL engine on the same chip (≤1e-4), served
outputs against the direct per-tenant path (2e-5 absolute, labels equal to
model.predict), and incremental onboarding against a from-scratch
run_protocol on the same anchor (≤1e-5). The compiled step-3 program must
hold the Mosaic kernel (tpu_custom_call).

    python chip_smoke.py               # one chip: fit -> serve -> onboard
    python chip_smoke.py --four-chips  # only the silo-sharded FL plan on a
                                       # (4, 1) mesh vs the unsharded plan

Each phase prints its wall time and checks; the last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script fails (non-zero exit, no result line) when the first device is
not a TPU, and names the failed phases when a check fails. Timings are one
smoke run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

REPO = pathlib.Path(__file__).resolve().parent
SEED = 0
DATASET = "mnist"
GROUPS, USERS, ROWS = 5, 4, 1000          # d × c × n_ij (paper layout)
ANCHOR_R = 2000
ROUNDS, LOCAL_EPOCHS = 3, 1


class Report:
    """Phase timings and checks; a check that misses its bar fails the
    phase it belongs to."""

    def __init__(self) -> None:
        self.failed: list = []

    def check(self, phase: str, name: str, value, bar, ok: bool) -> None:
        print(f"  check {name}: {value} (bar {bar}) "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        if not ok and phase not in self.failed:
            self.failed.append(phase)

    def run(self, phase: str, fn, *args):
        """Time one phase; an exception fails it and is re-raised, since
        every later phase depends on its output."""
        print(f"phase {phase}", flush=True)
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:
            traceback.print_exc()
            self.failed.append(phase)
            raise
        finally:
            print(f"  {phase}: {time.perf_counter() - t0:.3f} s", flush=True)
        return out


def rel_diff_tree(a, b) -> float:
    """max over leaves of max|a-b| / max|a| — fed_bench's rel_param_diff."""
    import jax
    import numpy as np
    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y)))
              / (np.max(np.abs(np.asarray(x))) + 1e-12))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def make_data():
    from repro.data.partition import split_iid
    from repro.data.tabular import make_dataset, train_test_split

    need = GROUPS * USERS * ROWS
    ds = make_dataset(DATASET, n=need + 2 * ROWS, seed=SEED)
    (Xtr, Ytr), (Xte, _) = train_test_split(ds, need + ROWS, ROWS, seed=SEED)
    Xs, Ys = split_iid(Xtr[:need], Ytr[:need], d=GROUPS, c=[USERS] * GROUPS,
                       n_ij=ROWS, seed=SEED)
    return ds.cfg, Xs, Ys, (Xtr[need:], Ytr[need:]), Xte


def phase_fit(rep: Report, cfg, Xs, Ys):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import FedDCL
    from repro.core import federated, protocol
    from repro.core.federated import bucket_pow2, pad_silo_data
    from repro.kernels.gram import ops as gram_ops
    from repro.models import mlp
    from repro.optim import adamw

    model = FedDCL(m_tilde=cfg.reduced_dim, hidden=cfg.hidden,
                   task=cfg.task, out_dim=cfg.out_dim, rounds=ROUNDS,
                   local_epochs=LOCAL_EPOCHS, anchor_r=ANCHOR_R,
                   svd_backend="device", engine="scan", seed=SEED)
    t0 = time.perf_counter()
    setup, result = model.fit(Xs, Ys)
    jax.block_until_ready(result.params)
    print(f"  fit (steps 1-4): {time.perf_counter() - t0:.3f} s, "
          f"final loss {result.history[-1]['loss']:.6f}", flush=True)

    # the Gram reduction of step 3 is the Mosaic kernel, not the reference
    width = USERS * cfg.reduced_dim
    hlo = gram_ops.gram_eigh_topk_batched.lower(
        jnp.zeros((GROUPS, ANCHOR_R, width), jnp.float32),
        k=cfg.reduced_dim).compile().as_text()
    rep.check("fit", "step3 program holds tpu_custom_call",
              "tpu_custom_call" in hlo, True, "tpu_custom_call" in hlo)

    # step 3 (device, f32) vs the host backend (NumPy f64) on the same inputs
    t0 = time.perf_counter()
    host = protocol.run_protocol(Xs, Ys, m_tilde=cfg.reduced_dim,
                                 anchor_r=ANCHOR_R, seed=SEED,
                                 svd_backend="host", anchor=setup.anchor)
    rel = max(float(np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-12))
              for a, b in zip(host.collab_X, setup.collab_X))
    print(f"  host step 1-3 reference: {time.perf_counter() - t0:.3f} s",
          flush=True)
    rep.check("fit", "step3 device vs host rel_frobenius", f"{rel:.3e}",
              "1e-3", rel <= 1e-3)

    # step 4: the cached scan plan vs the host engine on the SAME bucketed
    # layout (tests/test_plan_cache.py), on this chip
    t0 = time.perf_counter()
    silos = setup.fed_silos()
    bs = model.batch_size
    n_max = max(x.shape[0] for x, _ in silos)
    padded = pad_silo_data(silos, bs,
                           min_batches=bucket_pow2(-(-n_max // bs)),
                           min_silos=bucket_pow2(len(silos)))
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, cfg.task)
    init = mlp.init_mlp_params(jax.random.PRNGKey(SEED), cfg.reduced_dim,
                               cfg.hidden, cfg.out_dim)
    host_fl = federated._run_host(
        federated._make_batch_loss(loss, True, 0.0), init, padded,
        opt=adamw(model.lr), rounds=ROUNDS, local_epochs=LOCAL_EPOCHS,
        aggregator="fedavg", seed=SEED, eval_fn=None, per_example=True,
        reset_opt=True)
    print(f"  host FL engine reference: {time.perf_counter() - t0:.3f} s",
          flush=True)
    rel = rel_diff_tree(host_fl.params, result.params)
    rep.check("fit", "scan plan vs host engine rel_param_diff", f"{rel:.3e}",
              "1e-4", rel <= 1e-4)
    return model


def _check_served(rep: Report, phase: str, model, out, cases) -> None:
    """Served logits vs the direct per-tenant path (transform on host, MLP
    forward) at tests/test_serve_collab.py's bar, and labels vs predict."""
    import numpy as np

    from repro.models import mlp

    statuses = set(out.status.values())
    rep.check(phase, "all requests done", sorted(statuses), ["done"],
              statuses == {"done"})
    # one forward over every case's rows: per-case calls would compile the
    # un-jitted forward once per row count
    h = np.concatenate([model.transform(x, g, u) for _, g, u, x in cases])
    ref = np.asarray(mlp.mlp_forward(model.params_, h.astype(np.float32)))
    got = np.concatenate([out[rid] for rid, _, _, _ in cases])
    err = float(np.max(np.abs(got - ref)))
    rep.check(phase, "served vs direct max_abs_err",
              f"{err:.3e} (max |logit| {np.max(np.abs(ref)):.3e})", "2e-5",
              err <= 2e-5)
    _, g, u, x = cases[0]
    miss = int(np.sum(got.argmax(-1) != ref.argmax(-1)))
    miss += int(np.sum(out[cases[0][0]].argmax(-1) != model.predict(x, g, u)))
    rep.check(phase, "served labels != direct / predict", miss, 0, miss == 0)


def _submit(srv, rng, Xte, tenants, n_requests, max_rows=64):
    max_rows = min(max_rows, Xte.shape[0] // 2)
    cases = []
    for _ in range(n_requests):
        g, u = tenants[int(rng.integers(0, len(tenants)))]
        lo = int(rng.integers(0, Xte.shape[0] - max_rows))
        x = Xte[lo:lo + int(rng.integers(1, max_rows + 1))]
        cases.append((srv.submit(x, g, u).rid, g, u, x))
    return cases


def phase_serve(rep: Report, model, Xte):
    import numpy as np

    from repro.analysis import CompileCounter

    srv = model.serve(max_batch=256)
    tenants = [(g, u) for g in range(GROUPS) for u in range(USERS)]

    def stream():
        rng = np.random.default_rng(SEED + 1)
        cases = _submit(srv, rng, Xte, tenants, 64)
        return srv.serve(), cases

    t0 = time.perf_counter()
    out, cases = stream()
    print(f"  served {len(out)} requests, {srv.stats()['rows_served']} rows "
          f"(cold): {time.perf_counter() - t0:.3f} s", flush=True)
    _check_served(rep, "serve", model, out, cases)
    with CompileCounter() as cc:                  # warm replay
        t0 = time.perf_counter()
        stream()
        dt = time.perf_counter() - t0
    print(f"  warm replay: {dt:.3f} s", flush=True)
    rep.check("serve", "warm replay executable builds", cc.count, 0,
              cc.count == 0)
    return srv


def phase_onboard(rep: Report, srv, model, cfg, Xs, Ys, new, Xte):
    import numpy as np

    from repro.core import protocol

    Xn, Yn = new
    t0 = time.perf_counter()
    j = srv.onboard_user(0, Xn, Yn)
    print(f"  onboard_user(0): {time.perf_counter() - t0:.3f} s", flush=True)
    rng = np.random.default_rng(SEED + 2)
    cases = _submit(srv, rng, Xte, [(0, j)], 8)
    _check_served(rep, "onboard", model, srv.serve(), cases)

    # incremental == from-scratch recompute over the grown roster on the
    # same anchor (tests/test_onboard.py's device bar)
    inc = model.setup_
    Xs2 = [list(r) for r in Xs]
    Ys2 = [list(r) for r in Ys]
    Xs2[0].append(Xn)
    Ys2[0].append(Yn)
    t0 = time.perf_counter()
    ref = protocol.run_protocol(Xs2, Ys2, m_tilde=cfg.reduced_dim,
                                anchor_r=ANCHOR_R, seed=SEED,
                                svd_backend="device", anchor=inc.anchor)
    print(f"  from-scratch recompute: {time.perf_counter() - t0:.3f} s",
          flush=True)

    def rel(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return float(np.abs(a - b).max() / max(1.0, float(np.abs(b).max())))

    err = rel(inc.Z, ref.Z)
    for i in range(ref.num_groups):
        for jj in range(ref.num_users(i)):
            err = max(err, rel(inc.Gs[i][jj], ref.Gs[i][jj]))
        err = max(err, rel(inc.collab_X[i], ref.collab_X[i]))
    rep.check("onboard", "incremental vs recompute max_rel_err",
              f"{err:.3e}", "1e-5", err <= 1e-5)


def phase_four_chips(rep: Report):
    """The silo-sharded FL plan on a (4, 1) host mesh (d=5 padded to 8
    silos) vs the same plan unsharded on one device, fedavg and median.

    The optimizer is plain SGD: Adam, whose state restarts every round,
    turns ulp-level differences into lr-sized sign flips on near-zero
    gradients. Even under SGD this ReLU network is chaotic at ulp scale:
    the fedavg boundary sums partial sums in another order when sharded
    (~1e-7 apart), and one more round turns that into ~1e-3, just as
    moving the unsharded plan's init by one ulp does (printed below). So
    fedavg is held to the bar over ONE round, where sharding is the only
    difference; median gathers every silo and reduces in one order, so it
    is held to the bar over all rounds, which checks the per-round
    schedule and weights of the sharded plan."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.analysis import collective_census
    from repro.configs.feddcl_mlp import PAPER_MLPS
    from repro.core import federated
    from repro.core.federated import pad_silo_data, run_federated
    from repro.data.tabular import make_dataset
    from repro.launch.mesh import make_host_mesh
    from repro.models import mlp
    from repro.optim import sgd

    cfg = PAPER_MLPS[DATASET]
    mesh = make_host_mesh(model=1)
    if mesh.devices.shape != (4, 1):
        raise RuntimeError(f"expected a (4, 1) mesh, got {mesh.devices.shape}")
    # MNIST stand-in rows mapped to the collaboration width m̂ by a seeded
    # linear map: the FL phase sees the shapes the protocol would give it
    ds = make_dataset(DATASET, n=GROUPS * USERS * ROWS, seed=SEED)
    rng = np.random.default_rng(SEED)
    proj = rng.standard_normal((cfg.in_dim, cfg.reduced_dim)) / np.sqrt(
        cfg.in_dim)
    rows = USERS * ROWS
    silos = [(ds.X[i * rows:(i + 1) * rows] @ proj,
              ds.Y[i * rows:(i + 1) * rows]) for i in range(GROUPS)]
    params = mlp.init_mlp_params(jax.random.PRNGKey(SEED), cfg.reduced_dim,
                                 cfg.hidden, cfg.out_dim)
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, cfg.task)
    leaves = len(jax.tree.leaves(params))
    bs = 32
    padded = pad_silo_data(silos, bs, min_silos=8)
    args = federated._plan_args(padded, SEED, ROUNDS)
    for agg in ("fedavg", "median"):
        rounds = 1 if agg == "fedavg" else ROUNDS
        kw = dict(opt=sgd(1e-2), rounds=rounds, local_epochs=LOCAL_EPOCHS,
                  batch_size=bs, engine="scan", seed=SEED, aggregator=agg)
        t0 = time.perf_counter()
        base = run_federated(loss, params, silos, **kw)
        jax.block_until_ready(base.params)
        t1 = time.perf_counter()
        sh = run_federated(loss, params, silos, mesh=mesh, **kw)
        jax.block_until_ready(sh.params)
        print(f"  {agg}: unsharded {t1 - t0:.3f} s, sharded "
              f"{time.perf_counter() - t1:.3f} s (cold, incl. compile)",
              flush=True)
        rel = rel_diff_tree(base.params, sh.params)
        rep.check("four_chips", f"{agg} sharded vs unsharded rel_param_diff "
                  f"over {rounds} round(s)", f"{rel:.3e}", "1e-5",
                  rel <= 1e-5)
        if agg == "fedavg":
            # the ulp-scale chaos the docstring describes, measured
            kw["rounds"] = ROUNDS
            many = run_federated(loss, params, silos, **kw).params
            sh_many = run_federated(loss, params, silos, mesh=mesh,
                                    **kw).params
            ulp = jax.tree.map(lambda a: np.nextafter(
                np.asarray(a), np.float32(np.inf)), params)
            moved = run_federated(loss, ulp, silos, **kw).params
            print(f"  fedavg over {ROUNDS} rounds: sharded vs unsharded "
                  f"{rel_diff_tree(many, sh_many):.3e}; unsharded vs "
                  f"unsharded from an init one ulp away "
                  f"{rel_diff_tree(many, moved):.3e}", flush=True)

        plan = federated.make_fl_plan(
            num_silos=padded.num_silos, num_batches=padded.num_batches,
            batch_size=padded.batch_size, opt=sgd(1e-2),
            batch_loss=federated._make_batch_loss(loss, True, 0.0),
            rounds=ROUNDS, local_epochs=LOCAL_EPOCHS, aggregator=agg,
            masked=True, mesh=mesh)
        # the program the plan emits (tests/test_fed_sharded.py's counts):
        # fedavg psums every param leaf and the loss; median gathers every
        # leaf and the availability mask, and psums the loss
        lowered = plan.lower(params, *args)
        want = ({"all-reduce": leaves + 1} if agg == "fedavg"
                else {"all-reduce": 1, "all-gather": leaves + 1})
        census = collective_census(lowered.as_text(dialect="hlo"))
        rep.check("four_chips", f"{agg} collective census (program)", census,
                  want, census == want)
        # what runs on the chips: the TPU compiler turns the 8-float mask
        # gather into an all-reduce and combines it with the loss psum, so
        # the count of collective operands is what it keeps
        compiled = lowered.compile()
        ran = collective_census(compiled)
        ok = (set(ran) <= set(want)
              and sum(ran.values()) == sum(want.values()))
        rep.check("four_chips", f"{agg} collective census (compiled)", ran,
                  f"{sum(want.values())} operands of {sorted(want)}", ok)
    # the silo stack really spans 4 devices: the compiled plan takes X
    # sharded over "data", and placing it so puts 2 silos on each chip
    x_sharding = compiled.input_shardings[0][1]
    X = jax.device_put(args[0], NamedSharding(mesh, P("data")))
    devs = {s.device for s in X.addressable_shards}
    per_shard = {s.data.shape[0] for s in X.addressable_shards}
    ok = (len(x_sharding.device_set) == 4 and len(devs) == 4
          and per_shard == {padded.num_silos // 4}
          and x_sharding.is_equivalent_to(X.sharding, X.ndim))
    rep.check("four_chips", "silo shards span devices",
              f"{len(devs)} devices x {sorted(per_shard)} silos", "4 x [2]",
              ok)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the silo-sharded FL plan on 4 chips")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the first device is {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; this smoke has no CPU mode",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(jax.devices()) < need:
        print(f"chip_smoke: needs {need} TPU chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              f"checkout of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    import jax.monitoring

    from repro.analysis import CompileCounter
    from repro.api import enable_persistent_compilation_cache

    cache_dir = enable_persistent_compilation_cache()
    disk_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: disk_hits.append(1)
        if event == "/jax/compilation_cache/cache_hits" else None)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)

    rep = Report()
    t0 = time.perf_counter()
    try:
        with CompileCounter() as builds:
            if args.four_chips:
                rep.run("four_chips", phase_four_chips, rep)
            else:
                cfg, Xs, Ys, new, Xte = rep.run("data", make_data)
                model = rep.run("fit", phase_fit, rep, cfg, Xs, Ys)
                srv = rep.run("serve", phase_serve, rep, model, Xte)
                rep.run("onboard", phase_onboard, rep, srv, model, cfg, Xs,
                        Ys, new, Xte)
    except Exception:
        pass                                # recorded in rep.failed
    print(f"total: {time.perf_counter() - t0:.3f} s; executables obtained "
          f"{builds.count}, of which compile-cache disk hits "
          f"{len(disk_hits)}", flush=True)
    if rep.failed:
        print(f"chip_smoke: FAILED phases: {', '.join(rep.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
