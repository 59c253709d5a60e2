"""The harness: refusals, and that a new cell needs only new files.

    PYTHONPATH=src python -m pytest bench/tests -q
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

from bench import common, run

ROOT = common.ROOT
DATA = pathlib.Path(__file__).parent / "data"


def _cli(cwd: pathlib.Path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), "--workload",
         "har_d5c4.protocol", "--seed", "3", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc) -> bool:
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_a_first_device_that_is_not_a_tpu_is_refused():
    proc = _cli(ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "not a TPU" in proc.stderr


def test_a_checkout_of_the_benchmark_alone_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    proc = _cli(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)


def _digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_config_a_mix_and_a_metric_are_new_files_and_entries(tmp_path):
    """A later change adds a configuration, a traffic mix of an existing
    kind, a cell's limits and a per-layer metric as new files, plus
    entries in BENCHMARK.json; no file the benchmark has is edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    before = _digest(tmp_path / "bench")
    b = tmp_path / "bench"
    shutil.copy(DATA / "tiny.json", b / "configs" / "tiny_new.json")
    (b / "traffic" / "rebuild_pairs.json").write_text(json.dumps(
        {"kind": "protocol_rebuild", "warmup_builds": 1, "sample": 2,
         "trace_seconds": 1}))
    name = "tiny_new.rebuild_pairs"
    (b / "limits" / f"{name}.json").write_text(json.dumps(
        {"basis_gap": 1e-3, "z_gap": 1e-3, "g_gap": 1e-3, "xhat_gap": 1e-3}))
    (b / "metrics" / "protocol.builds_per_s.py").write_text(
        "def read(ctx):\n"
        "    c = ctx['counters']\n"
        "    return c['builds'] / c['seconds'] if c.get('builds') else None\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_new", "source": "tests",
                            "file": "bench/configs/tiny_new.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": name, "config": "tiny_new",
                              "traffic": "rebuild_pairs", "chips": 1,
                              "why": "tests"})
    for m in spec["end_to_end"]:
        if m["name"] == "protocol_s":
            m["workloads"].append(name)
    spec["per_layer"].append({
        "name": "protocol.builds_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "collaboration solve",
        "moves": "protocol_s", "workloads": [name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    args = argparse.Namespace(workload=name, seed=2 ** 33 + 7, seconds=1.0,
                              trace=0)
    out = run.run_cell(args, root=tmp_path)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"protocol_s", "setup_s"}
    assert list(out)[-1] == "checks"
    read = run.load_reader("protocol.builds_per_s", tmp_path)
    assert read({"counters": {"builds": 6, "seconds": 2.0}}) == 3.0
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
