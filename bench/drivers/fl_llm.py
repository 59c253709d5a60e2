"""FedDCL rounds of an LLM backbone on one chip's share of a DC server's
model, back to back, through the call ``launch/train.py`` makes
(``compile_round`` and ``run_rounds``).

The configuration's file names the architecture in the program's registry
(``arch``) and states every number it runs with, the published config's
keys among them, cut to one chip: its layer count, the experts held here
(``n_routed_experts``, from ``deployment.first_held_expert``, of
``deployment.router_experts`` the router scores) and the vocabulary slice
(``vocab_size``); ``ModelConfig.with_overrides`` applies them. Weights
are the benchmark's own (bench/reference/mla_moe.py), drawn from the seed
on the device, router bias included; the program trains from them. Each round is
H local steps of B sequences of the configuration's length, token ids
drawn fresh per step from a Zipf law (s from the traffic) over the slice,
then the boundary. Set-up compiles the round and runs round 0, the
compared round; the window runs further rounds, and ``fl_samples_per_s``
is the sequences they trained over the time from the window's start to the
end of its last round.

Correctness: after the program's state is released, the reference trains
round 0 from the same weights on the same tokens in float32 at the highest
precision, and the comparison takes

- ``loss_gap``, ``gnorm_gap``: the relative gaps of the first local
  step's loss and gradient norm (before clipping), before any update:
  they hold the forward and backward passes to the precision the
  configuration states;
- ``steps_gap``: the largest relative gap of a later local step's loss or
  gradient norm. Adam's first update moves every weight by about the
  learning rate along its gradient's sign, and the sign of a gradient
  near nought is a coin toss at bfloat16, so later steps drift apart by
  ten times the first's gap on a sound program (PERF.md §4);
- ``change_gap``: the gap between the norms of each leaf's change over the
  round, by the worst leaf, against the larger of that leaf's reference
  norm and the median leaf's.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import common
from bench.drivers import base
from bench.reference import mla_moe as ref


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        tr = cfg["train"]
        self.shape = ref.shape_of(cfg)
        self.hp = (tr["lr"], tr["b1"], tr["b2"], tr["eps"],
                   tr["weight_decay"], tr["grad_clip"])
        self.H, self.B = traffic["local_steps"], traffic["sequences"]
        self.S = tr["seq"]
        ranks = np.arange(1, self.shape.vocab + 1, dtype=np.float64)
        p = ranks ** -float(traffic["zipf_s"])
        self.cdf = np.cumsum(p / p.sum())
        self.rounds = 0
        self.window_rounds = 0
        self.round_s = None

    # -- data and weights ------------------------------------------------------

    def batches(self, r: int) -> dict:
        """Round r's tokens and next-token labels, (H, 1 silo, B, S) int32:
        Zipf ranks over the slice, the most frequent id 0."""
        rng = np.random.default_rng(common.subseed(self.seed, "tokens", r))
        u = rng.random((self.H, 1, self.B, self.S + 1))
        ids = np.minimum(np.searchsorted(self.cdf, u), self.shape.vocab - 1)
        ids = ids.astype(np.int32)
        return {"tokens": ids[..., :-1], "labels": ids[..., 1:]}

    def weights(self):
        return ref.init_weights(
            jax.random.PRNGKey(common.subseed(self.seed, "weights")),
            self.shape, float(self.cfg["train"]["router_bias_std"]))

    def model_config(self):
        """The registry's architecture (``arch``) with every number the
        file states, the cut among them."""
        from repro.configs import get_arch
        from repro.configs.base import MLAConfig
        c, dep = self.cfg, self.cfg["deployment"]
        arch = get_arch(c["arch"])
        moe = dataclasses.replace(
            arch.moe, num_experts=dep["router_experts"],
            top_k=c["num_experts_per_tok"],
            d_ff_expert=c["moe_intermediate_size"],
            num_shared_experts=c["n_shared_experts"],
            d_ff_shared=c["moe_intermediate_size"],
            router=c["scoring_func"],
            routed_scaling=c["routed_scaling_factor"],
            held_experts=c["n_routed_experts"],
            first_held=dep["first_held_expert"])
        mla = MLAConfig(q_lora_rank=c["q_lora_rank"] or 0,
                        kv_lora_rank=c["kv_lora_rank"],
                        qk_nope_head_dim=c["qk_nope_head_dim"],
                        qk_rope_head_dim=c["qk_rope_head_dim"],
                        v_head_dim=c["v_head_dim"])
        return arch.with_overrides(
            num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["v_head_dim"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            first_k_dense=c["first_k_dense_replace"],
            mtp_depth=c["num_nextn_predict_layers"],
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], moe=moe, mla=mla)

    # -- the program -----------------------------------------------------------

    def setup(self) -> None:
        from repro.configs.base import (FederatedConfig, InputShape,
                                        TrainConfig)
        from repro.core.federated import silo_replicate
        from repro.launch.train import compile_round, run_rounds
        from repro.models import backbone as bb

        tr = self.cfg["train"]
        mcfg = self.model_config()
        tc = TrainConfig(
            model=mcfg, shape=InputShape("fl_llm", self.S, self.B, "train"),
            federated=FederatedConfig(num_silos=1, local_steps=self.H,
                                      aggregator=tr["aggregator"]),
            learning_rate=tr["lr"], weight_decay=tr["weight_decay"],
            warmup_steps=0, total_steps=2 ** 30, grad_clip=tr["grad_clip"],
            param_dtype=self.cfg["precision"]["params"],
            opt_state_dtype=self.cfg["precision"]["opt_state"],
            compute_dtype=self.cfg["precision"]["compute"], remat=True)
        self._run = run_rounds
        self.step, opt = compile_round(mcfg, tc)
        w = self.weights()
        want = jax.eval_shape(lambda: bb.init_params(
            mcfg, jax.random.PRNGKey(0), jnp.float32))
        if jax.tree.structure(w) != jax.tree.structure(want) or any(
                a.shape != b.shape for a, b in
                zip(jax.tree.leaves(w), jax.tree.leaves(want))):
            raise ValueError("the benchmark's weights do not fit the "
                             "program's parameter layout")
        self.sp = silo_replicate(w, 1)
        del w
        self.so = jax.vmap(opt.init)(self.sp)
        self.compared = self._round()
        self.program_change = np.asarray(_silo_change(self.sp,
                                                      self.weights()))

    def _round(self) -> dict:
        with base.span("bench.fl_round"):
            self.sp, self.so, (got,) = self._run(
                self.step, self.sp, self.so, self.batches, 1,
                first=self.rounds)
        self.rounds += 1
        return got

    def window(self, seconds: float, trace_dir=None) -> dict:
        times = []
        with base.window_span(trace_dir):
            t0 = base.now()
            while base.now() - t0 < seconds:
                t1 = base.now()
                self._round()
                times.append(base.now() - t1)
            elapsed = base.now() - t0
        done = self.window_rounds = len(times)
        self.round_s = ((min(times), float(np.median(times)), max(times))
                        if times else None)
        seqs = done * self.H * self.B
        return {"end_to_end": {"fl_samples_per_s": seqs / elapsed},
                "sequences": seqs, "seconds": elapsed, "rounds": done}

    def notes(self) -> dict:
        return {"FL rounds in the window": self.window_rounds,
                "sequences per round": self.H * self.B,
                "round seconds min/median/max": self.round_s}

    def release(self) -> None:
        self.sp = self.so = self.step = self._run = None
        gc.collect()
        jax.clear_caches()

    def attempted_failed(self):
        return self.rounds, 0

    # -- the reference ---------------------------------------------------------

    def reference(self, **kw) -> dict:
        b = self.batches(0)
        return ref.local_round(self.weights,
                               {k: v[:, 0] for k, v in b.items()},
                               self.shape, self.hp, **kw)

    def numbers(self, got: dict, want: dict) -> dict:
        """got, want: per-step "loss" and "grad_norm", per-leaf "change"."""
        loss = np.abs(got["loss"] - want["loss"]) / np.abs(want["loss"])
        gn = np.abs(got["grad_norm"] - want["grad_norm"]) / want["grad_norm"]
        den = np.maximum(want["change"], np.median(want["change"]))
        ch = np.abs(got["change"] - want["change"]) / den
        self.each = {"loss_each": loss.tolist(), "gnorm_each": gn.tolist(),
                     "worst_leaf": int(np.argmax(ch)),
                     "bias_changed": want.get("bias_changed")}
        return {"loss_gap": float(loss[0]), "gnorm_gap": float(gn[0]),
                "steps_gap": float(max(loss[1:].max(), gn[1:].max())),
                "change_gap": float(ch.max())}

    def program(self) -> dict:
        c = self.compared
        return {"loss": np.asarray(c["loss"], np.float64).reshape(-1),
                "grad_norm": np.asarray(c["grad_norm"],
                                        np.float64).reshape(-1),
                "change": self.program_change}

    def check(self, limits: dict) -> dict:
        self._ref = self.reference()
        nums = self.numbers(self.program(), self._ref)
        return {k: base.check_entry(v, limits, k) for k, v in nums.items()}

    def controls(self) -> dict:
        """The control and each planted fault, computed by the reference in
        the program's place, against the reference."""
        want = getattr(self, "_ref", None) or self.reference()
        out = {}
        for kind, kw in [("control", {"lowp": True})] + [
                (f, {"fault": f}) for f in ref.FAULTS]:
            out[kind] = dict(self.numbers(self.reference(**kw), want),
                             **self.each)
        return out


@jax.jit
def _silo_change(sp, w):
    """Each leaf's change norm of the (one) silo's params from `w`."""
    return ref.change_norms(jax.tree.map(lambda a: a[0], sp), w)
