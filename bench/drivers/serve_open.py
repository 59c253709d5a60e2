"""Online scoring for every tenant: an open loop of requests into a live
``ServeCollab`` server.

Set-up builds the deployment from the seed, runs steps 1-3 once (the
program's device backend), starts ``ServeCollab`` with the benchmark's
seeded network weights, serves one request at every power-of-two batch
width up to ``max_batch`` (each width is one resident program; all groups
share them), and freezes what set-up left on the heap (``gc.freeze``).

Traffic (the mix's parameters): Poisson arrivals at ``rate_per_s`` for the
window; each request's tenant drawn Zipf(``zipf_s``) over the deployment's
tenants (popularity order drawn from the seed); its rows 1 with
probability ``p_single``, otherwise log-uniform over 2..``max_rows``,
taken from held-out rows of the deployment's distribution. One loop
submits every request that is due, then steps the server once; idle, it
sleeps until the next arrival. A request's latency runs from its due time
to the end of the server step that put its last row on the host; one not
done ``grace_s`` after the window is failed (and counted at no less than
its wait until then).
``serve_p95_ms`` is the 95th percentile over every request due in the
window.

Correctness: a sample of the finished requests (drawn from the seed, and
the longest request besides) is scored by the plain reference: the
reference's own steps 1-3 (float64) give each tenant's map, and the
network runs in float64 on the benchmark's weights. ``logit_gap`` is the
largest |served − reference| over the sample's logits, against the
largest |reference logit|.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from bench import common
from bench.drivers import base
from bench.reference import mlp as ref_mlp
from bench.reference import protocol as ref_protocol


def arrivals(traffic: dict, seconds: float, seed: int, tenants: int):
    """(due seconds, tenant index, rows, pool offset) of each request."""
    rng = np.random.default_rng(common.subseed(seed, "arrivals"))
    rate = float(traffic["rate_per_s"])
    n = int(rng.poisson(rate * seconds))
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    ranks = np.arange(1, tenants + 1, dtype=np.float64)
    p = ranks ** -float(traffic["zipf_s"])
    order = rng.permutation(tenants)
    tenant = order[rng.choice(tenants, size=n, p=p / p.sum())]
    lo, hi = math.log(2), math.log(int(traffic["max_rows"]) + 1)
    many = np.floor(np.exp(rng.uniform(lo, hi, size=n))).astype(int)
    rows = np.where(rng.uniform(size=n) < float(traffic["p_single"]), 1,
                    np.clip(many, 2, int(traffic["max_rows"])))
    offset = rng.integers(0, int(traffic["pool_rows"]) - rows + 1)
    return due, tenant, rows, offset


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        lay = cfg["layout"]
        self.tenants = [(i, j) for i in range(lay["groups"])
                        for j in range(lay["users_per_group"])]
        self.done = []
        self.failed = 0
        self.attempted = 0

    def setup(self) -> None:
        from repro.core import protocol
        from repro.serve_collab import ServeCollab
        cfg = self.cfg
        self.pseed = common.subseed(self.seed, "protocol")
        self.dep = common.make_deployment(
            cfg, self.seed, pool=int(self.traffic["pool_rows"]))
        setup = protocol.run_protocol(
            self.dep.Xs, self.dep.Ys, seed=self.pseed,
            svd_backend=cfg["protocol"]["step3"],
            **common.protocol_kwargs(cfg))
        self.params = ref_mlp.init_params(
            jax.random.PRNGKey(common.subseed(self.seed, "weights")),
            tuple(ref_mlp.dims(cfg)))
        self.max_batch = int(self.traffic["max_batch"])
        self.srv = ServeCollab.from_setup(setup, self.params,
                                          max_batch=self.max_batch)
        pool = self.dep.pool_X.astype(np.float32)
        width = 1
        while width <= self.max_batch:
            self.srv.submit(pool[:width], 0, 0)
            self.srv.serve()
            width *= 2
        self.pool = pool
        # the generator and the server share this process: a full
        # collection over everything set-up left on the heap stalls the
        # loop for 50-70 ms and queues every request due meanwhile, so the
        # set-up's objects are moved out of the collector's way
        gc.collect()
        gc.freeze()

    def window(self, seconds: float, trace_dir=None) -> dict:
        srv = self.srv
        due, tenant, rows, offset = arrivals(self.traffic, seconds, self.seed,
                                             len(self.tenants))
        n = len(due)
        reqs = [None] * n
        late = np.zeros(n)
        steps0, rows0 = srv.steps, srv.rows_served
        step_s = 0.0
        grace = float(self.traffic["grace_s"])
        backlog = {}              # queued rows at half the window and at its close
        i = 0
        stall = 0.0               # the longest pass of the loop
        with base.window_span(trace_dir), base.gc_pauses() as gcp:
            t0 = base.now()
            last = t0
            while True:
                t = base.now() - t0
                stall = max(stall, t0 + t - last)
                last = t0 + t
                for mark in (0.5, 1.0):
                    if mark not in backlog and t >= mark * seconds:
                        backlog[mark] = sum(r.rows - r.served
                                            for r in srv.queue)
                while i < n and due[i] <= t:
                    g, u = self.tenants[tenant[i]]
                    with base.span("bench.submit"):
                        reqs[i] = srv.submit(
                            self.pool[offset[i]:offset[i] + rows[i]], g, u)
                    late[i] = t - due[i]
                    i += 1
                if srv.queue:
                    with base.span("bench.serve_step"):
                        s0 = base.now()
                        srv.step()
                        step_s += base.now() - s0
                elif i < n:
                    time.sleep(max(0.0, min(due[i] - (base.now() - t0),
                                            1e-3)))
                else:
                    break
                if t > seconds + grace:
                    break
            t_end = base.now() - t0
        queued_rows = sum(r.rows - r.served for r in srv.queue)
        # a request not done counts as failed; its latency is at least the
        # time from its due time to the loop's end
        lat = t_end - due
        done = np.zeros(n, bool)
        for k, r in enumerate(reqs):
            if r is not None and r.status == "done":
                lat[k] = r.t_done - (t0 + due[k])
                done[k] = True
                self.done.append((k, r))
        self.attempted, self.failed = n, int(np.sum(~done))
        steps = srv.steps - steps0
        self.stats = {
            "requests": n, "rows": int(np.sum(rows)),
            "late_p50_ms": float(np.percentile(late[:i], 50) * 1e3) if i else 0.0,
            "late_max_ms": float(np.max(late[:i]) * 1e3) if i else 0.0,
            "drain_s": t_end - seconds, "left_rows": int(queued_rows),
            "backlog_mid_rows": int(backlog.get(0.5, 0)),
            "backlog_close_rows": int(backlog.get(1.0, 0)),
            "p50_ms": float(np.percentile(lat, 50) * 1e3) if n else 0.0,
            "loop_stall_max_ms": stall * 1e3,
            "gc_max_ms": max(gcp, default=0.0) * 1e3,
            "gc_total_ms": sum(gcp) * 1e3}
        p95 = float(np.percentile(lat, 95) * 1e3)
        return {"end_to_end": {"serve_p95_ms": p95},
                "steps": steps, "rows_served": srv.rows_served - rows0,
                "step_s": step_s, "seconds": t_end, **self.stats}

    def notes(self) -> dict:
        return {f"serve {k}": v for k, v in self.stats.items()}

    def release(self) -> None:
        self.srv = None

    def attempted_failed(self):
        return self.attempted, self.failed

    def _picked(self):
        """The sample: `sample` finished requests drawn from the seed, and
        the longest."""
        rng = np.random.default_rng(common.subseed(self.seed, "pick"))
        k = min(int(self.traffic["sample"]), len(self.done))
        idx = set(rng.choice(len(self.done), size=k, replace=False).tolist())
        if self.done:
            idx.add(max(range(len(self.done)),
                        key=lambda q: self.done[q][1].rows))
        return [self.done[q][1] for q in sorted(idx)]

    def _reference_logits(self, picked, lowp: bool = False):
        """Each picked request's logits: the reference's own steps 1-3 give
        the tenant's map, then the network on the benchmark's weights, all
        in float64 (bfloat16-rounded operands for the control)."""
        ref = ref_protocol.collaborate(
            self.dep.Xs, seed=self.pseed, lowp=lowp,
            **common.protocol_kwargs(self.cfg))
        hs = [ref_protocol.mm(np.asarray(r.x, np.float64)
                              - ref.maps[r.group][r.user].mu[None, :],
                              ref.tables[r.group][r.user], lowp)
              for r in picked]
        z = ref_mlp.forward_np(self.params, np.concatenate(hs), lowp)
        return np.split(z, np.cumsum([len(h) for h in hs])[:-1])

    @staticmethod
    def _gap(got, want) -> float:
        scale = max(float(np.max(np.abs(w))) for w in want)
        return max(float(np.max(np.abs(np.asarray(g, np.float64) - w)))
                   for g, w in zip(got, want)) / scale

    def check(self, limits: dict) -> dict:
        picked = self._picked()
        if not picked:
            return {"logit_gap": {"value": float("inf"),
                                  "limit": float(limits["logit_gap"])}}
        gap = self._gap([r.out for r in picked],
                        self._reference_logits(picked))
        return {"logit_gap": base.check_entry(gap, limits, "logit_gap")}

    def controls(self) -> dict:
        picked = self._picked()
        return {"control": {"logit_gap": self._gap(
            self._reference_logits(picked, lowp=True),
            self._reference_logits(picked))}}
