import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes, print memory/cost analysis, dump roofline terms.

The two lines above MUST stay the first statements in this file — jax locks
the device count at first backend initialization, and the 512 placeholder
host devices exist ONLY for the dry-run (smoke tests and benchmarks see the
real single device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--mode feddcl]
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes --out results/dryrun

Exit code is non-zero if any requested pair fails to lower+compile — the
dry-run IS the test of distribution-config coherence.
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, INPUT_SHAPES
from repro.configs.base import FederatedConfig, TrainConfig
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh, num_silos
from repro.launch.specs import make_plan, resolve_arch_for_shape

# The chip the dry-run models: the production meshes are v5e pods, compiled
# here on placeholder host devices, so the roofline takes v5e's peaks.
TARGET_DEVICE_KIND = "TPU v5 lite"


def run_pair(arch: str, shape_name: str, *, multi_pod: bool, mode: str,
             out_dir: str | None, verbose: bool = True,
             scan_only: bool = False, moe_impl: str | None = None,
             tag: str = "", variant: str | None = None) -> dict:
    import dataclasses as _dc

    cfg = ARCHS[arch]
    if variant == "rwkv_seq":        # §Perf: sequence-parallel WKV chunks
        cfg = cfg.with_overrides(ssm=_dc.replace(cfg.ssm, shard="seq"))
    elif variant == "expand_kv":     # §Perf: head-parallel decode, replicated cache
        cfg = cfg.with_overrides(decode_expand_kv=True)
    elif variant == "cache_seq":     # §Perf: sequence-sharded decode cache
        cfg = cfg.with_overrides(decode_cache_seq=True)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size

    # deepseek-v3 cannot hold fp32 AdamW moments at 256 chips — bf16 moments
    # (DESIGN.md §5; the memory_analysis printout is the receipt).
    opt_dtype = "bfloat16" if arch == "deepseek-v3-671b" else "float32"
    tc = TrainConfig(model=cfg, shape=shape, param_dtype="bfloat16",
                     compute_dtype="bfloat16", opt_state_dtype=opt_dtype,
                     federated=FederatedConfig(num_silos=num_silos(mesh),
                                               local_steps=4))
    from repro.models.layers import unrolled

    # Two compiles per pair (measured in this container, see EXPERIMENTS.md
    # §Dry-run methodology):
    #  * scan-over-layers -> memory_analysis peak is liveness-accurate
    #    (while-loop buffers are reused per iteration);
    #  * statically unrolled -> cost_analysis FLOPs/bytes and the HLO
    #    collective set are trip-count-honest (XLA counts loop bodies ONCE),
    #    but the CPU backend's scheduler inflates unrolled temp memory.
    t0 = time.perf_counter()
    plan = make_plan(cfg, shape, mesh, mode=mode, tc=tc, moe_impl=moe_impl)

    def compile_plan(unroll: bool):
        import contextlib
        ctx = unrolled() if unroll else contextlib.nullcontext()
        # fresh closure per compile: the unroll flag is a trace-time global,
        # so the two builds must not share a jit cache entry
        fn = plan.step_fn
        wrapped = lambda *a: fn(*a)  # noqa: E731
        with mesh, ctx:
            jitted = jax.jit(wrapped,
                             in_shardings=plan.in_shardings,
                             out_shardings=plan.out_shardings,
                             donate_argnums=plan.donate_argnums)
            return jitted.lower(*plan.args).compile()

    # scan_only: one compile (memory + compile-success proof); cost numbers
    # then carry the while-loop undercount and are flagged in the record.
    compiled_scan = compile_plan(unroll=False)  # memory source
    compiled = compiled_scan if scan_only else compile_plan(unroll=True)
    t1 = time.perf_counter()

    # silo boundary: contiguous pod block (multi-pod) or data row (single-pod)
    silo_block = 256 if multi_pod else 16
    rec = roofline.analyze(
        compiled, resolve_arch_for_shape(cfg, shape), shape, plan.kind,
        chips=chips, device_kind=TARGET_DEVICE_KIND, silo_block=silo_block,
        local_steps=tc.federated.local_steps if plan.kind == "fed_local" else 1)
    ma_scan = compiled_scan.memory_analysis()
    rec["memory"] = {
        "argument_bytes": ma_scan.argument_size_in_bytes,
        "output_bytes": ma_scan.output_size_in_bytes,
        "temp_bytes": ma_scan.temp_size_in_bytes,
        "alias_bytes": ma_scan.alias_size_in_bytes,
    }
    compiled = compiled_scan   # memory printout below reports the scan build
    rec.update({
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": mode,
        "plan": plan.name,
        "compile_s": t1 - t0,
        "cost_source": "scan(undercounts loops)" if scan_only else "unrolled",
    })
    if verbose:
        ma = compiled.memory_analysis()
        print(f"== {plan.name} mesh={rec['mesh']} chips={chips} "
              f"compile={rec['compile_s']:.1f}s")
        print(f"   memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}GiB "
              f"out={ma.output_size_in_bytes/2**30:.2f}GiB "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB "
              f"alias={ma.alias_size_in_bytes/2**30:.2f}GiB  (per device)")
        print(f"   cost_analysis: flops/dev={rec['flops_per_device']:.3e} "
              f"bytes/dev={rec['bytes_per_device']:.3e} "
              f"coll_bytes/dev={rec['collective_bytes_per_device']:.3e}")
        print("   " + roofline.fmt_row(rec))
        sys.stdout.flush()
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = f"{arch}__{shape_name}__{rec['mesh']}__{mode}{suffix}.json"
        with open(os.path.join(out_dir, fn), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), action="append")
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), action="append")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="baseline",
                    choices=["baseline", "feddcl", "feddcl_sync"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--scan-only", action="store_true",
                    help="single compile per pair (compile-proof + memory; "
                         "cost numbers carry the while-loop undercount)")
    ap.add_argument("--moe-impl", default=None, choices=["gspmd", "ep", "dense"])
    ap.add_argument("--variant", default=None,
                    choices=["rwkv_seq", "expand_kv", "cache_seq"])
    ap.add_argument("--tag", default="", help="suffix for output JSON names")
    args = ap.parse_args()

    archs = sorted(ARCHS) if (args.all or not args.arch) else args.arch
    shapes = sorted(INPUT_SHAPES) if (args.all or not args.shape) else args.shape
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            if args.mode == "feddcl" and INPUT_SHAPES[shape].kind != "train":
                continue
            for mp in meshes:
                try:
                    run_pair(arch, shape, multi_pod=mp, mode=args.mode,
                             out_dir=args.out, scan_only=args.scan_only,
                             moe_impl=args.moe_impl, tag=args.tag,
                             variant=args.variant)
                except Exception as e:  # noqa: BLE001 — report and continue
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"!! FAIL {arch} {shape} multi_pod={mp}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        return 1
    print("\nall requested dry-runs compiled OK")
    return 0


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    sys.exit(main())
