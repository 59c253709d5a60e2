"""Kernel micro-benchmarks: wall time of the jnp reference paths on CPU
(the Pallas kernels target TPU; interpret mode is a correctness harness, not
a perf path — noted in the CSV as 'interpret')."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _time(fn, *args, iters: int = 5, repeats: int = 3) -> float:
    """Best-of-`repeats` mean over `iters` calls — the min filters out CPU
    scheduling noise that would otherwise swamp sub-ms kernels."""
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters * 1e6)   # us
    return best


def run(fast: bool = False):
    rows = []
    ks = jax.random.split(jax.random.PRNGKey(0), 5)

    from repro.kernels.flash_attention import ops as fa
    B, S, H, KV, hd = 1, 512 if fast else 1024, 8, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    us = _time(lambda a, b, c: fa.flash_attention(a, b, c, backend="ref"), q, k, v)
    flops = 4 * B * S * S * H * hd
    rows.append(("flash_attention_ref_xla", us, f"{flops/us/1e3:.1f}GFLOP/s"))

    from repro.kernels.gram import ops as gr
    a = jax.random.normal(ks[3], (2000, 256), jnp.float32)
    us = _time(lambda x: gr.gram(x, backend="ref"), a)
    rows.append(("gram_ref_xla", us, f"{2*2000*256*256/us/1e3:.1f}GFLOP/s"))

    # batched collaboration engine vs the legacy per-group Python loop
    # (d groups of stacked anchor representations, protocol step 3a sizes)
    d, r, m = 16, 2000, 32
    ab = jax.random.normal(ks[3], (d, r, m), jnp.float32)
    us_loop = _time(
        lambda x: [gr.gram(x[i], backend="ref") for i in range(d)], ab,
        iters=10)
    us_bat = _time(lambda x: gr.gram_batched(x, backend="ref"), ab, iters=10)
    rows.append(("gram_group_loop_d16", us_loop, f"{d}x dispatch"))
    rows.append(("gram_batched_d16", us_bat,
                 f"speedup={us_loop/max(us_bat,1e-9):.1f}x"))

    from repro.kernels.rwkv6 import ops as rw
    B, S, Hh, K = 1, 256 if fast else 1024, 4, 64
    r = jax.random.normal(ks[0], (B, S, Hh, K))
    kk = jax.random.normal(ks[1], (B, S, Hh, K))
    vv = jax.random.normal(ks[2], (B, S, Hh, K))
    lw = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, S, Hh, K)), -8, 1.6))
    u = jax.random.normal(ks[4], (Hh, K)) * 0.3
    us_scan = _time(lambda *x: rw.wkv6(*x, backend="scan"), r, kk, vv, lw, u,
                    iters=2, repeats=1)
    us_chunk = _time(lambda *x: rw.wkv6(*x, backend="chunked"), r, kk, vv, lw,
                     u, iters=2, repeats=1)
    rows.append(("wkv6_scan_oracle", us_scan, "sequential"))
    rows.append(("wkv6_chunked_xla", us_chunk,
                 f"speedup={us_scan/max(us_chunk,1e-9):.1f}x"))
    return rows


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
