"""End-to-end driver: train a ~100M-parameter llama-family model for a few
hundred steps on the synthetic pipeline under the FedDCL federated schedule
(2 silos, H=4) and write the loss curve to results/e2e_driver.json.

~100M config: 8 layers, d_model 512, 8 heads (kv 4), d_ff 2048, vocab 32768.
On this CPU container a full run takes tens of minutes; --steps trims it.

  PYTHONPATH=src python examples/end_to_end_driver.py --steps 200
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.configs.base import FederatedConfig, InputShape, TrainConfig
from repro.core.federated import silo_replicate
from repro.data.tokens import silo_batches
from repro.launch import steps as steps_lib
from repro.models import backbone as bb


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--silos", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--out", default="results/e2e_driver.json")
    args = ap.parse_args()

    cfg = get_arch("llama3.2-1b").with_overrides(
        name="llama-100m", num_layers=8, d_model=512, num_heads=8,
        num_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32768)
    shape = InputShape("e2e", seq_len=args.seq, global_batch=args.batch,
                       kind="train")
    tc = TrainConfig(model=cfg, shape=shape, learning_rate=1e-3,
                     warmup_steps=20, total_steps=args.steps,
                     param_dtype="float32", compute_dtype="float32",
                     remat=False,
                     federated=FederatedConfig(num_silos=args.silos,
                                               local_steps=args.local_steps))

    params = bb.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    print(f"params: {bb.count_params_analytic(cfg)/1e6:.1f}M")
    vstep, opt = steps_lib.make_federated_local_step(cfg, tc)
    sync = steps_lib.make_fedavg_sync_step(tc)
    vstep = jax.jit(vstep, donate_argnums=(0, 1))
    sync = jax.jit(sync, donate_argnums=(0, 1))

    sp = silo_replicate(params, args.silos)
    so = jax.vmap(opt.init)(sp)
    hist = []
    t0 = time.perf_counter()
    for step in range(args.steps):
        nb = silo_batches(cfg.vocab_size, args.seq, args.batch // args.silos,
                          args.silos, step, non_iid=True)
        b = {k: jnp.asarray(v) for k, v in nb.items()}
        sp, so, m = vstep(sp, so, b)
        if (step + 1) % args.local_steps == 0:
            sp, so = sync(sp, so)
        if step % 10 == 0 or step == args.steps - 1:
            loss = float(jnp.mean(m["loss"]))
            hist.append({"step": step, "loss": loss,
                         "elapsed_s": time.perf_counter() - t0})
            print(f"step {step:4d} loss {loss:.4f} ({hist[-1]['elapsed_s']:.0f}s)")

    import os
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"config": "llama-100m", "history": hist}, f, indent=1)
    print(f"-> {args.out}: loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
