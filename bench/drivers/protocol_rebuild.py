"""Steps 1-3 of FedDCL from scratch, build after build, on one roster.

Set-up builds the deployment from the seed and runs ``warmup_builds``
builds (the first compiles every step-3 program). The window runs
``run_protocol`` over the whole roster back to back, each build with its
own seed, so each draws its own anchor and private rotations (same
shapes). ``protocol_s`` is the window over the builds it completed.

Correctness: a sample of the window's builds, drawn from the seed, is
compared with the plain reference (bench/reference/protocol.py) run with
the same build seed on the same roster; each number is the worst relative
Frobenius gap over the sample: every group's basis B̃ (``basis_gap``), the
central target Z (``z_gap``), every user's G (``g_gap``) and every group's
X̂ (``xhat_gap``).
"""
from __future__ import annotations

from types import SimpleNamespace

import jax

from bench import common
from bench.drivers import base
from bench.drivers.sampling import Reservoir
from bench.reference import protocol as ref_protocol


def view(setup) -> SimpleNamespace:
    """A program FedDCLSetup (built with onboarding state) seen as the
    reference's Collaboration."""
    return SimpleNamespace(bases=setup.onboard.bases_B, Z=setup.Z,
                           Gs=setup.Gs, collab_X=setup.collab_X,
                           grams=setup.onboard.grams)


def gaps(got, want) -> dict:
    """Worst relative gaps of steps 1-3's outputs, got against want; a
    roster of another shape (a user missing or extra) reads infinite."""
    shape = lambda c: [len(gs) for gs in c.Gs]
    if shape(got) != shape(want):
        return dict.fromkeys(("basis_gap", "z_gap", "g_gap", "xhat_gap"),
                             float("inf"))
    return {
        "basis_gap": max(base.rel_gap(b, r)
                         for b, r in zip(got.bases, want.bases)),
        "z_gap": base.rel_gap(got.Z, want.Z),
        "g_gap": max(base.rel_gap(g, r) for gs, rs in zip(got.Gs, want.Gs)
                     for g, r in zip(gs, rs)),
        "xhat_gap": max(base.rel_gap(x, r)
                        for x, r in zip(got.collab_X, want.collab_X)),
    }


def merge_max(rows) -> dict:
    out: dict = {}
    for r in rows:
        for k, v in r.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.sample = Reservoir(traffic["sample"], common.subseed(seed, "pick"))
        self.builds = 0
        self.window_builds = 0

    def _build_seed(self, k: int) -> int:
        return common.subseed(self.seed, "build", k)

    def _build(self):
        from repro.core import protocol
        k = self.builds
        with base.span("bench.protocol_build"):
            st = protocol.run_protocol(
                self.dep.Xs, self.dep.Ys, seed=self._build_seed(k),
                svd_backend=self.cfg["protocol"]["step3"], onboard=True,
                **common.protocol_kwargs(self.cfg))
            jax.block_until_ready(st.onboard.g_factors[-1]["q"])
        self.builds += 1
        return k, st

    def setup(self) -> None:
        self.dep = common.make_deployment(self.cfg, self.seed)
        for _ in range(int(self.traffic["warmup_builds"])):
            self._build()

    def window(self, seconds: float, trace_dir=None) -> dict:
        done = 0
        with base.window_span(trace_dir):
            t0 = base.now()
            while base.now() - t0 < seconds:
                self.sample.offer(self._build())
                done += 1
            elapsed = base.now() - t0
        self.window_builds = done
        return {"end_to_end": {"protocol_s": elapsed / max(done, 1)},
                "builds": done, "seconds": elapsed}

    def notes(self) -> dict:
        return {"builds in the window": self.window_builds,
                "builds compared": len(self.sample.items)}

    def release(self) -> None:
        pass

    def attempted_failed(self):
        return self.window_builds, 0

    def _reference(self, k: int, lowp: bool = False):
        return ref_protocol.collaborate(
            self.dep.Xs, seed=self._build_seed(k), lowp=lowp,
            **common.protocol_kwargs(self.cfg))

    def check(self, limits: dict) -> dict:
        nums = merge_max(gaps(view(st), self._reference(k))
                         for k, st in self.sample.items)
        return {k: base.check_entry(v, limits, k) for k, v in nums.items()}

    def controls(self) -> dict:
        return {"control": merge_max(
            gaps(self._reference(k, lowp=True), self._reference(k))
            for k, _ in self.sample.items)}
