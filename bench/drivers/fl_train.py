"""Step 4 of FedDCL: federated training between the DC servers, phase after
phase, through the call ``FedDCL.fit`` makes.

Set-up builds the deployment from the seed, runs steps 1-3 once (the
program's device backend) to make every group's X̂, and drives the first
``compared_phases`` FL phases; the first compiles the plan, which the plan
cache then hands to every later phase. Each phase trains the network from
its own initial weights (seeded by the run's seed and the phase index) for
the configuration's rounds × local epochs, with its own batch schedule.
The window runs further phases back to back; ``fl_samples_per_s`` is the
real samples they trained (rows × local epochs × rounds per phase) over the
time from the window's start to the end of the last phase.

Correctness: the reference trains the compared phases (set-up's
``compared_phases``) from the same weights on the same X̂ and labels:

- ``loss_r0_gap``: relative gap of the first round's loss (before any
  round boundary), the least over the compared phases: the per-silo Adam
  steps. The network is chaotic under Adam: in a phase where no unit
  crosses a kink differently, program and reference agree to float32
  rounding (~1e-8); elsewhere a bifurcation in the later local epochs
  moves the loss by 1e-5 to 4e-3. A sound program reproduces the first
  round in some phase; one that computes in lower precision, or another
  step, does so in none (PERF.md §4);
- ``loss_r1_gap``: relative gap of the second round's loss, which starts
  from the first boundary's average, the median over the compared phases:
  the FedAvg boundary;
- ``change_gap``: over the first ``change_phases`` phases, the gap between
  the norms of the parameters' change over the whole phase, by the worst
  leaf, against the larger of that leaf's reference norm and the median
  leaf's.

Leaves whose reference gradient is under a thousandth of the median leaf's
would move by round-off alone and are left out of ``change_gap``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import common
from bench.drivers import base
from bench.reference import mlp as ref_mlp


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.tr = cfg["train"]
        self.widths = tuple(ref_mlp.dims(cfg))
        self.compared = []          # (init, final params, round losses)
        self.phases = 0

    # -- the program ---------------------------------------------------------

    def setup(self) -> None:
        from repro.core import protocol
        from repro.core.federated import run_federated
        from repro.models import mlp
        from repro.optim import adamw

        cfg, tr = self.cfg, self.tr
        dep = common.make_deployment(cfg, self.seed)
        setup = protocol.run_protocol(
            dep.Xs, dep.Ys, seed=common.subseed(self.seed, "protocol"),
            svd_backend=cfg["protocol"]["step3"],
            **common.protocol_kwargs(cfg))
        self.silos = setup.fed_silos()
        self.labels = [np.concatenate(ys) for ys in dep.Ys]
        self.samples = sum(len(y) for y in self.labels) * \
            tr["local_epochs"] * tr["rounds"]
        del setup, dep
        opt = adamw(tr["lr"], b1=tr["b1"], b2=tr["b2"], eps=tr["eps"])
        task = "classification"
        # the arguments FedDCL.fit passes (repro/api.py)
        self._fit = partial(
            run_federated, partial(mlp.mlp_per_example_loss, task=task),
            opt=opt, rounds=tr["rounds"], local_epochs=tr["local_epochs"],
            batch_size=tr["batch"], aggregator=tr["aggregator"],
            engine="scan", cache=True,
            loss_id=("mlp_per_example_loss", task),
            opt_id=("adamw", tr["lr"]))
        for _ in range(int(self.traffic["compared_phases"])):
            p0, res = self._phase()
            self.compared.append((p0, res.params,
                                  np.array([h["loss"] for h in res.history])))

    def _init(self, k: int):
        return ref_mlp.init_params(
            jax.random.PRNGKey(common.subseed(self.seed, "init", k)),
            self.widths)

    def _phase(self):
        k = self.phases
        p0 = self._init(k)
        with base.span("bench.fl_phase"):
            res = self._fit(p0, self.silos,
                            seed=common.subseed(self.seed, "phase", k))
            jax.block_until_ready(res.params)
        self.phases += 1
        return p0, res

    def window(self, seconds: float, trace_dir=None) -> dict:
        times = []
        with base.window_span(trace_dir):
            t0 = base.now()
            while base.now() - t0 < seconds:
                t1 = base.now()
                self._phase()
                times.append(base.now() - t1)
            elapsed = base.now() - t0
        done = self.window_phases = len(times)
        self.phase_s = ((min(times), float(np.median(times)), max(times))
                        if times else None)
        return {"end_to_end": {
                    "fl_samples_per_s": done * self.samples / elapsed},
                "samples": done * self.samples, "seconds": elapsed,
                "phases": done, "params": ref_mlp.param_count(self.widths)}

    def notes(self) -> dict:
        return {"FL phases in the window": self.window_phases,
                "real samples per phase": self.samples,
                "phase seconds min/median/max": self.phase_s}

    def release(self) -> None:
        from repro.core.federated import clear_plan_cache
        clear_plan_cache()
        self._fit = None

    def attempted_failed(self):
        return self.window_phases + len(self.compared), 0

    # -- the reference ---------------------------------------------------------

    def n_slots(self) -> int:
        """A cached run's slots per silo: batches rounded up to a power of
        two (the plan cache's canonical layout), times the batch."""
        nb = -(-max(len(y) for y in self.labels) // self.tr["batch"])
        return (1 << (nb - 1).bit_length()) * self.tr["batch"]

    def reference(self, k: int, lowp: bool = False, fault=None,
                  rounds=None):
        tr = self.tr
        silos = [(np.asarray(x, np.float32), y)
                 for (x, _), y in zip(self.silos, self.labels)]
        return ref_mlp.fedavg(
            self._init(k), silos, rounds=rounds or tr["rounds"],
            local_epochs=tr["local_epochs"], batch=tr["batch"], lr=tr["lr"],
            b1=tr["b1"], b2=tr["b2"], eps=tr["eps"],
            key=jax.random.PRNGKey(common.subseed(self.seed, "phase", k)),
            n_slots=self.n_slots(), lowp=lowp, fault=fault)

    def held_leaves(self):
        """Leaves the change comparison holds: reference gradient at the
        first phase's weights at least a thousandth of the median leaf's."""
        x, _ = self.silos[0]
        g = ref_mlp.first_grad_norms(self._init(0), jnp.asarray(x, jnp.float32),
                                     jnp.asarray(self.labels[0]),
                                     self.tr["batch"])
        med = float(np.median(g))
        return [i for i, v in enumerate(g) if v >= 1e-3 * med]

    def reference_runs(self, **kw):
        """(round losses of the first two rounds of every compared phase,
        final params of the first `change_phases`) of the reference."""
        losses = [self.reference(k, rounds=2, **kw)[1]
                  for k in range(len(self.compared))]
        finals = [self.reference(k, **kw)[0]
                  for k in range(int(self.traffic["change_phases"]))]
        return losses, finals

    def numbers(self, got, want) -> dict:
        """got: per compared phase (init, final, round losses); want: the
        reference's (losses, finals)."""
        losses, finals = want
        r0 = [abs(lg[0] - lr[0]) / abs(lr[0])
              for (_, _, lg), lr in zip(got, losses)]
        r1 = [abs(lg[1] - lr[1]) / abs(lr[1])
              for (_, _, lg), lr in zip(got, losses)]
        held = self.held_leaves()
        ch = 0.0
        for (p0, pg, _), pr in zip(got, finals):
            init = jax.tree.leaves(p0)
            ng = [float(np.linalg.norm(np.asarray(a) - np.asarray(b)))
                  for a, b in zip(jax.tree.leaves(pg), init)]
            nr = [float(np.linalg.norm(np.asarray(a) - np.asarray(b)))
                  for a, b in zip(jax.tree.leaves(pr), init)]
            med = float(np.median(nr))
            ch = max([ch] + [abs(ng[i] - nr[i]) / max(nr[i], med)
                             for i in held])
        self.each = {"r0_each": r0, "r1_each": r1}
        return {"loss_r0_gap": float(np.min(r0)),
                "loss_r1_gap": float(np.median(r1)), "change_gap": ch}

    def check(self, limits: dict) -> dict:
        self._ref = self.reference_runs()
        nums = self.numbers(self.compared, self._ref)
        return {k: base.check_entry(v, limits, k) for k, v in nums.items()}

    def controls(self) -> dict:
        """The control and the planted faults, each in the program's place
        against the reference (a state left unchanged reads 1 on
        change_gap by construction and needs no run)."""
        want = getattr(self, "_ref", None) or self.reference_runs()
        out = {}
        for kind, kw in (("control", {"lowp": True}),
                         ("half_batch", {"fault": "half_batch"}),
                         ("no_boundary", {"fault": "no_boundary"})):
            losses, finals = self.reference_runs(**kw)
            got = [(self._init(k), finals[k] if k < len(finals) else None,
                    losses[k]) for k in range(len(losses))]
            out[kind] = dict(self.numbers(got, want), **self.each)
        return out
