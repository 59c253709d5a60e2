"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload mnist_d5c4.fl --seed 7 --seconds 20 --trace 0

The cell (BENCHMARK.json ``workloads``) names a configuration and a traffic
mix; both are data files found by name (bench/configs/<config>.json,
bench/traffic/<traffic>.json). The mix names the driver that runs it
(bench/drivers/<kind>.py); the cell's correctness limits are in
bench/limits/<workload>.json and each per-layer metric is read by
bench/metrics/<metric>.py.

A run: refuse a first device that is not a TPU, or fewer chips than the
cell asks for (non-zero exit, no result line); build the deployment and
warm every shape the window uses from the seed (``setup_s``); measure for
``--seconds`` (with ``--trace 1`` under the profiler, for the per-layer
metrics); count executables built inside the window; then compare what the
window produced with the plain reference (``correct``). The last stdout
line is one JSON object; the numbers compared are the last stderr lines.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".trace"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def load_reader(name: str, root: pathlib.Path = ROOT):
    """bench/metrics/<name>.py's ``read`` (names may hold dots)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int):
    """The first device must be a TPU and there must be `chips` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"the first device is {devs[0].platform!r} "
                           f"({devs[0].device_kind}), not a TPU")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, found {len(devs)}")
    return devs


def run_cell(args, *, root: pathlib.Path = ROOT, cell_override=None) -> dict:
    """One run of one cell on the devices JAX finds (``main`` has refused
    any that are not the cell's TPUs); returns the result object.
    `cell_override` (spec, cell, cfg, traffic, limits) replaces the files a
    workload name finds (the tests drive small sizes through it)."""
    import jax

    from bench import common, trace_reduce
    spec, cell, cfg, traffic, limits = (
        cell_override or common.load_cell(args.workload, root))
    devs = jax.devices()
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    driver_mod = importlib.import_module(f"bench.drivers.{traffic['kind']}")
    drv = driver_mod.Driver(cfg, traffic, args.seed)
    drv.setup()
    setup_s = time.perf_counter() - T_START

    builds = trace_reduce.BuildCounter()
    trace_dir = None
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        trace_dir = TRACE_DIR / f"{cell['name']}-{args.seed}"
    with builds:
        window = drv.window(seconds, trace_dir)
    print(f"bench: executables built inside the window: {builds.count}",
          flush=True)
    for k, v in drv.notes().items():
        print(f"bench: {k}: {v}", flush=True)
    dev0 = devs[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell["chips"]])

    drv.release()
    t_ref = time.perf_counter()
    checks = drv.check(limits)
    print(f"bench: reference and comparison took "
          f"{time.perf_counter() - t_ref:.3f} s", flush=True)
    attempted, failed = drv.attempted_failed()
    correct = (failed == 0 and builds.count == 0 and
               all(c["value"] <= c["limit"] for c in checks.values()))
    if builds.count:
        print(f"bench: {builds.count} executables were built inside the "
              f"window; a warm-up is missing", file=sys.stderr)

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    names = (spec["per_layer"] if args.trace else spec["end_to_end"])
    mine = [m for m in names
            if cell["name"] in m.get("workloads", [cell["name"]])]
    metrics = {}
    if args.trace:
        red = trace_reduce.reduce_dir(trace_dir)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        ctx = {"trace": red, "counters": window, "cfg": cfg,
               "traffic": traffic, "device_kind": dev0.device_kind}
        for m in mine:
            val = load_reader(m["name"], root)(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        e2e = dict(window["end_to_end"], setup_s=setup_s)
        for m in mine:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if args.trace:
        out["breakdown"] = red.breakdown()
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT))
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program beside {ROOT / 'bench'}: run from a "
                    f"checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    # the compile cache lives at a fixed path inside the checkout; the
    # program's enable_persistent_compilation_cache takes the directory set
    # here
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    try:
        import jax
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        from bench import common
        spec, cell, *_ = common.load_cell(args.workload, ROOT)
        check_device(cell["chips"])
    except (RuntimeError, KeyError, FileNotFoundError) as e:
        return fail(str(e))
    out = run_cell(args)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
