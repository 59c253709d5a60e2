"""Tenant churn on a live deployment: one new user admitted at a time.

Set-up builds the deployment from the seed, runs steps 1-3 once with
onboarding state (the program's device backend), keeps a deep copy of that
setup, and admits ``warmup_admissions`` newcomers (the first compiles every
program an admission runs). Admission k restores a live server from the
copy (outside the timed span) and admits newcomer k mod ``newcomers``
(``rows_per_user`` rows) into group k mod d with ``ServeCollab.onboard_user``,
which grows the group's Gram, refactors, re-solves every G, refreshes X̂
and the serving tables. Every admission starts from the same deployment,
so every one has the same shapes. ``onboard_s`` is the admissions' total
timed wall time over their number.

Correctness: a sample of the window's admissions, drawn from the seed, is
compared with the plain reference run from scratch over the grown roster
on the deployment's anchor: the grown group's Gram and every other
(``gram_gap``), every basis, Z, every G and X̂ (as the protocol cell), and
every group's serving table (``table_gap``: each tenant's combined map and
offset, against W·G and the mean of the reference).
"""
from __future__ import annotations

import copy

import jax
import numpy as np

from bench import common
from bench.drivers import base
from bench.drivers.protocol_rebuild import gaps, merge_max, view
from bench.drivers.sampling import Reservoir
from bench.reference import mlp as ref_mlp
from bench.reference import protocol as ref_protocol


def table_gap(tables, want) -> float:
    """Worst relative gap of the live serving tables (each group's
    combined maps M and offsets mu, real tenants only) against W·G and mu
    of the reference."""
    worst = 0.0
    if [t.count for t in tables] != [len(m) for m in want.maps]:
        return float("inf")
    for t, maps, ref in zip(tables, want.maps, want.tables):
        n = t.count
        worst = max(worst, base.rel_gap(np.asarray(t.M)[:n], ref),
                    base.rel_gap(np.asarray(t.mu)[:n],
                                 np.stack([f.mu for f in maps])))
    return worst


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.sample = Reservoir(traffic["sample"], common.subseed(seed, "pick"))
        self.k = 0
        self.window_done = 0

    def setup(self) -> None:
        from repro.core import protocol
        cfg = self.cfg
        self.pseed = common.subseed(self.seed, "protocol")
        self.dep = common.make_deployment(
            cfg, self.seed, newcomers=int(self.traffic["newcomers"]))
        live = protocol.run_protocol(
            self.dep.Xs, self.dep.Ys, seed=self.pseed,
            svd_backend=cfg["protocol"]["step3"], onboard=True,
            **common.protocol_kwargs(cfg))
        self.base = copy.deepcopy(live)
        self.params = ref_mlp.init_params(
            jax.random.PRNGKey(common.subseed(self.seed, "weights")),
            tuple(ref_mlp.dims(cfg)))
        for _ in range(int(self.traffic["warmup_admissions"])):
            self._admit()

    def _restore(self):
        from repro.serve_collab import ServeCollab
        with base.span("bench.restore"):
            return ServeCollab.from_setup(copy.deepcopy(self.base),
                                          self.params, max_batch=256)

    def _admit(self):
        """One admission on a freshly restored server; returns
        (k, group, newcomer, server, timed seconds)."""
        k = self.k
        g = k % self.cfg["layout"]["groups"]
        t = k % len(self.dep.new_X)
        srv = self._restore()
        with base.span("bench.onboard"):
            t0 = base.now()
            srv.onboard_user(g, self.dep.new_X[t], self.dep.new_Y[t])
            jax.block_until_ready([tb.M for tb in srv.tables])
            dt = base.now() - t0
        self.k += 1
        return k, g, t, srv, dt

    def window(self, seconds: float, trace_dir=None) -> dict:
        times = []
        with base.window_span(trace_dir):
            t0 = base.now()
            while base.now() - t0 < seconds:
                k, g, t, srv, dt = self._admit()
                times.append(dt)
                self.sample.offer((g, t, srv.setup, srv.tables))
            elapsed = base.now() - t0
        self.window_done = len(times)
        return {"end_to_end": {"onboard_s": sum(times) / max(len(times), 1)},
                "admissions": len(times), "timed_s": sum(times),
                "seconds": elapsed}

    def notes(self) -> dict:
        return {"admissions in the window": self.window_done,
                "admissions compared": len(self.sample.items)}

    def release(self) -> None:
        self.base = None

    def attempted_failed(self):
        return self.window_done, 0

    def _reference(self, g: int, t: int, lowp: bool = False):
        """From scratch over the grown roster, on the anchor the deployment
        fixed when it started (the reference's own step 1 on the first
        roster)."""
        kw = common.protocol_kwargs(self.cfg)
        anchor = ref_protocol.uniform_anchor(self.dep.Xs, self.pseed,
                                             kw["anchor_r"])
        Xs = [list(row) for row in self.dep.Xs]
        Xs[g].append(self.dep.new_X[t])
        return ref_protocol.collaborate(Xs, seed=self.pseed, anchor=anchor,
                                        lowp=lowp, **kw)

    @staticmethod
    def _gram_gap(got, want) -> float:
        if [np.shape(a) for a in got.grams] != [np.shape(b)
                                                 for b in want.grams]:
            return float("inf")
        return max(base.rel_gap(a, b) for a, b in zip(got.grams, want.grams))

    def check(self, limits: dict) -> dict:
        rows = []
        for g, t, setup, tables in self.sample.items:
            want = self._reference(g, t)
            got = view(setup)
            rows.append(dict(gaps(got, want),
                             gram_gap=self._gram_gap(got, want),
                             table_gap=table_gap(tables, want)))
        nums = merge_max(rows)
        return {k: base.check_entry(v, limits, k) for k, v in nums.items()}

    def controls(self) -> dict:
        rows = []
        for g, t, _, _ in self.sample.items:
            want, low = self._reference(g, t), self._reference(g, t, True)
            rows.append(dict(
                gaps(low, want), gram_gap=self._gram_gap(low, want),
                table_gap=max(base.rel_gap(a, b) for a, b in
                              zip(low.tables, want.tables))))
        return {"control": merge_max(rows)}
