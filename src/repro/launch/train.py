"""Training driver: baseline data-parallel OR FedDCL federated (silo-local
steps + periodic cross-silo FedAvg), on whatever devices exist.

On this CPU container it trains real (reduced) models on the synthetic token
pipeline; on a TPU pod the same code runs the production mesh — only
--mesh differs. Used by examples/feddcl_llm_pretrain.py and the end-to-end
driver run recorded in EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 200 --batch 8 --seq 256 --silos 4 --local-steps 4
"""
from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import store
from repro.configs import ARCHS, REDUCED
from repro.configs.base import (FederatedConfig, InputShape, ModelConfig,
                                TrainConfig)
from repro.core.federated import _fetch, silo_replicate
from repro.data.tokens import TokenStream, silo_batches
from repro.launch import steps as steps_lib
from repro.models import backbone as bb
from repro.models.modality import synthetic_prefix

# what a round's host read brings back: per local step (and silo)
ROUND_READ = ("loss", "grad_norm", "expert_rows", "expert_rows_max")


def compile_round(cfg: ModelConfig, tc: TrainConfig):
    """One FedDCL round (H silo-local steps, then the boundary) as one
    jitted program that donates the silo params and optimizer state."""
    round_step, opt = steps_lib.make_federated_round_step(cfg, tc)
    return jax.jit(round_step, donate_argnums=(0, 1)), opt


def run_rounds(step, sp, so, batches_of, n: int, *, first: int = 0):
    """Run `n` compiled dispatches of FedDCL rounds back to back, from
    round `first`. For each: build its batches on the host and upload them
    (span ``feddcl.llm.prepare``); dispatch it and read back, in one host
    read, its per-step losses, gradient norms and rows of the held experts
    (span ``feddcl.llm.round``), which also add to the counters
    ``feddcl.llm.tokens``, ``.expert_rows`` and ``.expert_rows_max``.

    step: (sp, so, batches) -> (sp, so, metrics); batches_of(r) -> the
    batch pytree of round r. Returns (sp, so, [what each read brought])."""
    got = []
    for r in range(first, first + n):
        with obs.span("feddcl.llm.prepare"):
            batches = jax.device_put(batches_of(r))
        with obs.span("feddcl.llm.round"):
            sp, so, ms = step(sp, so, batches)
            m = _fetch({k: ms[k] for k in ROUND_READ if k in ms})
        obs.count("feddcl.llm.tokens", batches["tokens"].size)
        if "expert_rows" in m:
            obs.count("feddcl.llm.expert_rows", int(np.sum(m["expert_rows"])))
            obs.count("feddcl.llm.expert_rows_max",
                      int(np.sum(m["expert_rows_max"])))
        got.append(m)
    return sp, so, got


def train(arch: str, *, reduced: bool = True, steps: int = 100, batch: int = 8,
          seq: int = 256, silos: int = 1, local_steps: int = 4,
          rounds_per_dispatch: int = 1,
          lr: float = 3e-4, seed: int = 0, non_iid: bool = False,
          log_every: int = 10, checkpoint_path: str | None = None,
          log_path: str | None = None, param_dtype: str = "float32",
          compute_dtype: str = "float32"):
    cfg = (REDUCED if reduced else ARCHS)[arch]
    shape = InputShape("cli", seq_len=seq, global_batch=batch, kind="train")
    tc = TrainConfig(
        model=cfg, shape=shape, learning_rate=lr, warmup_steps=max(steps // 20, 5),
        total_steps=steps, param_dtype=param_dtype, compute_dtype=compute_dtype,
        federated=FederatedConfig(num_silos=silos, local_steps=local_steps),
        remat=False, seed=seed)

    key = jax.random.PRNGKey(seed)
    params = bb.init_params(cfg, key, jnp.dtype(param_dtype))
    n_params = bb.count_params_analytic(cfg)
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M silos={silos} "
          f"H={local_steps} batch={batch}x{seq}")

    history = []
    federated = silos > 1
    prefix = (lambda k, b: synthetic_prefix(k, cfg, b)) if cfg.prefix_frontend else None

    if federated:
        # One FedDCL round (H vmapped silo-local steps + the fedavg_sync
        # boundary) is ONE compiled dispatch — the launch-tier consumption
        # of the core.federated scan engine (DESIGN.md §4).
        round_step, opt = compile_round(cfg, tc)
        assert batch % silos == 0
        sp = silo_replicate(params, silos)
        so = jax.vmap(opt.init)(sp)
        t0 = time.perf_counter()

        def stacked_batches(step0, h):
            """Stack h consecutive per-silo batches with leading dim h."""
            nbs = [silo_batches(cfg.vocab_size, seq, batch // silos, silos,
                                step0 + i, seed=seed, non_iid=non_iid)
                   for i in range(h)]
            b = {k: np.stack([nb[k] for nb in nbs]) for k in nbs[0]}
            if prefix is not None:
                def step_prefix(k):
                    return jax.vmap(lambda kk: prefix(kk, batch // silos))(
                        jax.random.split(k, silos))
                pks = jnp.stack([jax.random.fold_in(key, step0 + i)
                                 for i in range(h)])
                b["prefix_embeds"] = jax.vmap(step_prefix)(pks)
            return b

        def log_round(step0, metrics):
            h = int(metrics["loss"].shape[0])
            for i in range(h):
                step = step0 + i
                if step % log_every == 0 or step == steps - 1:
                    rec = {"step": step,
                           "loss": float(np.mean(metrics["loss"][i])),
                           "elapsed_s": time.perf_counter() - t0}
                    history.append(rec)
                    print(f"step {step:5d} loss {rec['loss']:.4f} "
                          f"({rec['elapsed_s']:.1f}s)")

        rpd = max(rounds_per_dispatch, 1)
        if rpd > 1:
            # R rounds per dispatch: one lax.scan over round steps, metrics
            # silo-meaned to (R, H) scalars inside the scan (bounded memory)
            multi_step, _ = steps_lib.make_federated_multiround_step(cfg, tc)
            multi_step = jax.jit(multi_step, donate_argnums=(0, 1))

            def multiround_batches(step0, r, h):
                bs = [stacked_batches(step0 + i * h, h) for i in range(r)]
                return {k: jnp.stack([jnp.asarray(b[k]) for b in bs])
                        for k in bs[0]}

        n_rounds = steps // local_steps
        rnd = 0
        while rnd < n_rounds:
            step0 = rnd * local_steps
            if rpd > 1 and n_rounds - rnd >= rpd:
                sp, so, (metrics,) = run_rounds(
                    multi_step, sp, so,
                    lambda r: multiround_batches(r * local_steps, rpd,
                                                 local_steps), 1, first=rnd)
                for r in range(rpd):
                    log_round(step0 + r * local_steps,
                              jax.tree.map(lambda a, r=r: a[r], metrics))
                rnd += rpd
            else:
                sp, so, (metrics,) = run_rounds(
                    round_step, sp, so,
                    lambda r: stacked_batches(r * local_steps, local_steps),
                    1, first=rnd)
                log_round(step0, metrics)
                rnd += 1
        rem = steps % local_steps
        if rem:
            # trailing steps of an unfinished round: local steps, no sync —
            # same semantics as the old per-step loop
            phase, _ = steps_lib.make_federated_local_phase_step(cfg, tc)
            phase = jax.jit(phase, donate_argnums=(0, 1))
            sp, so, (metrics,) = run_rounds(
                phase, sp, so, lambda _: stacked_batches(steps - rem, rem), 1)
            log_round(steps - rem, metrics)
        params = jax.tree.map(lambda a: a[0], sp)
    else:
        step_fn, opt = steps_lib.make_train_step(cfg, tc)
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        opt_state = opt.init(params)
        stream = TokenStream(cfg.vocab_size, seq, batch, seed=seed)
        t0 = time.perf_counter()
        for step in range(steps):
            nb = stream.batch(step)
            b = {k: jnp.asarray(v) for k, v in nb.items()}
            if prefix is not None:
                b["prefix_embeds"] = prefix(jax.random.fold_in(key, step), batch)
            params, opt_state, metrics = step_fn(params, opt_state, b)
            if step % log_every == 0 or step == steps - 1:
                rec = {"step": step, "loss": float(metrics["loss"]),
                       "elapsed_s": time.perf_counter() - t0}
                history.append(rec)
                print(f"step {step:5d} loss {rec['loss']:.4f} "
                      f"({rec['elapsed_s']:.1f}s)")

    if checkpoint_path:
        store.save(checkpoint_path, params,
                   {"arch": cfg.name, "steps": steps, "reduced": reduced})
        print(f"checkpoint -> {checkpoint_path}")
    if log_path:
        os.makedirs(os.path.dirname(os.path.abspath(log_path)), exist_ok=True)
        with open(log_path, "w") as f:
            json.dump({"arch": cfg.name, "silos": silos, "H": local_steps,
                       "history": history}, f, indent=1)
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--silos", type=int, default=1)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--rounds-per-dispatch", type=int, default=1,
                    help="FedDCL rounds fused into one compiled dispatch "
                         "(lax.scan over round steps); 1 = one dispatch per "
                         "round (unchanged default)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log", default=None)
    args = ap.parse_args()
    train(args.arch, reduced=args.reduced, steps=args.steps, batch=args.batch,
          seq=args.seq, silos=args.silos, local_steps=args.local_steps,
          rounds_per_dispatch=args.rounds_per_dispatch,
          lr=args.lr, seed=args.seed, non_iid=args.non_iid,
          checkpoint_path=args.checkpoint, log_path=args.log)


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
