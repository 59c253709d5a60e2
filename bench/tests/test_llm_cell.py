"""The LLM cell (``moonlight_a3b_ep8.fl_llm``) at a size a test run holds:
bench/tests/data/tiny_llm.json, a small share of the same block that
states float32 compute, so that the program agrees with the reference to
rounding and the control (operands rounded to float8) stands apart.

- the program passes the cell's limits and the control fails one;
- a whole run with the timed path broken underneath reports
  ``correct: false``, once for each fault the comparison must catch
  (half of each batch left out among them);
- the cell's four readers, on hand-made counters, program tables and
  intervals.

    PYTHONPATH=src python -m pytest bench/tests/test_llm_cell.py -q
"""
from __future__ import annotations

import argparse
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench import common, run, trace_reduce
from bench.drivers import fl_llm

NAME = "moonlight_a3b_ep8.fl_llm"
DATA = pathlib.Path(__file__).parent / "data"
TINY = common.load_json(DATA / "tiny_llm.json")
TINY["precision"] = dict(TINY["precision"], compute="float32")
SMALL = {"local_steps": 2, "sequences": 2}


def _cell():
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    cell = {"name": NAME, "config": "tiny_llm", "traffic": "fl_llm",
            "chips": 1}
    traffic = dict(common.load_json(common.BENCH / "traffic" /
                                    "fl_llm.json"), **SMALL)
    limits = common.load_json(common.BENCH / "limits" / f"{NAME}.json")
    return spec, cell, TINY, traffic, limits


def test_the_program_passes_and_the_control_fails():
    spec, cell, cfg, traffic, limits = _cell()
    drv = fl_llm.Driver(cfg, traffic, 2 ** 31 + 5)
    drv.setup()
    drv.window(0.5, None)
    drv.release()
    got = drv.check(limits)
    assert all(c["value"] <= c["limit"] for c in got.values()), got
    ctl = drv.controls()["control"]
    assert any(ctl[k] > limits[k] for k in limits), (ctl, limits)


def _held_only(monkeypatch):
    from repro.models import layers
    probs = layers._router_probs

    def held_only(p, x2d, mo, **kw):
        mine = jnp.arange(mo.num_experts) - mo.first_held
        mine = (mine >= 0) & (mine < mo.num_held)
        return probs(dict(p, router_bias=jnp.where(
            mine, p["router_bias"], -1e9)), x2d, mo, **kw)
    monkeypatch.setattr(layers, "_router_probs", held_only)


def _shared_left_out(monkeypatch):
    from repro.models import layers
    mlp = layers.apply_mlp
    shared = TINY["n_shared_experts"] * TINY["moe_intermediate_size"]
    monkeypatch.setattr(layers, "apply_mlp", lambda p, x: jnp.zeros_like(x)
                        if p["w_gate"].shape[-1] == shared else mlp(p, x))


def _bias_ignored(monkeypatch):
    from repro.models import layers
    probs = layers._router_probs
    monkeypatch.setattr(layers, "_router_probs", lambda p, x2d, mo, **kw:
                        probs(dict(p, router_bias=jnp.zeros_like(
                            p["router_bias"])), x2d, mo, **kw))


def _capacity_dropping(monkeypatch):
    """Switch capacity 1.25 × T·k/E: a (token, slot) past it in its
    expert's queue, earliest tokens first, adds nothing."""
    from repro.models import layers
    probs = layers._router_probs

    def capped(p, x2d, mo, **kw):
        gates, idx, full = probs(p, x2d, mo, **kw)
        T = x2d.shape[0]
        cap = int(1.25 * T * mo.top_k / mo.num_experts)
        one = jax.nn.one_hot(idx.reshape(-1), mo.num_experts, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(one, 0) - 1) * one, -1).reshape(idx.shape)
        return jnp.where(pos < cap, gates, 0.0), idx, full
    monkeypatch.setattr(layers, "_router_probs", capped)


def _half_batch(monkeypatch):
    """Each local step trains on the first half of its sequences."""
    from repro.launch import train
    rounds = train.run_rounds

    def half(step, sp, so, batches_of, n, **kw):
        def cut(r):
            return {k: v[:, :, : v.shape[2] // 2]
                    for k, v in batches_of(r).items()}
        return rounds(step, sp, so, cut, n, **kw)
    monkeypatch.setattr(train, "run_rounds", half)


FAULTS = [_held_only, _shared_left_out, _bias_ignored, _capacity_dropping,
          _half_batch]


@pytest.mark.parametrize("fault", FAULTS,
                         ids=[f.__name__.strip("_") for f in FAULTS])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    args = argparse.Namespace(workload=NAME, seed=2 ** 32 + 11, seconds=0.5,
                              trace=0)
    out = run.run_cell(args, cell_override=_cell())
    assert out["correct"] is False, out["checks"]


# -- the readers ---------------------------------------------------------------

CFG = common.load_json(common.BENCH / "configs" / "moonlight_a3b_ep8.json")
TRAFFIC = common.load_json(common.BENCH / "traffic" / "fl_llm.json")
SPAN = {"n": 4, "total_s": 0.02, "self_s": 0.02, "max_s": 0.008}


def _trace(kernel_calls: int, each_s: float):
    """A window of 10 s: the kernel's calls back to back from t = 1 s,
    then one other op of 1 s."""
    ops, t = [], 1e9
    for _ in range(kernel_calls):
        ops.append(trace_reduce.Op("/device:TPU:0",
                                   "%grouped_matmul_pallas.3", t,
                                   t + each_s * 1e9))
        t += each_s * 1e9
    ops.append(trace_reduce.Op("/device:TPU:0", "%fusion.7", t, t + 1e9))
    spans = [trace_reduce.Span("bench.window", 0.0, 10e9)]
    return trace_reduce.reduce(spans, ops)


def _read(name, monkeypatch, table, trace=None, counters=None):
    monkeypatch.syspath_prepend(str(common.ROOT / "src"))
    from repro import obs
    monkeypatch.setattr(obs, "snapshot", lambda: table)
    ctx = {"trace": trace, "counters": counters or {}, "cfg": CFG,
           "traffic": TRAFFIC, "device_kind": "TPU v5 lite"}
    return run.load_reader(name)(ctx)


def test_llm_mfu_reads_the_window(monkeypatch):
    from bench import flops_llm
    got = _read("llm.mfu", monkeypatch, {},
                counters={"sequences": 32, "seconds": 8.0})
    want = 100.0 * flops_llm.train_per_sequence(CFG) * 4.0 / 197e12
    assert got == pytest.approx(want)
    assert _read("llm.mfu", monkeypatch, {}, counters={}) is None


def test_llm_prepare_ms_reads_its_span(monkeypatch):
    table = {"spans": {"feddcl.llm.prepare": SPAN}, "counters": {}}
    assert _read("llm.prepare_ms", monkeypatch, table) == pytest.approx(5.0)
    assert _read("llm.prepare_ms", monkeypatch,
                 {"spans": {}, "counters": {}}) is None


def test_llm_device_idle_reads_the_trace(monkeypatch):
    red = _trace(16, 0.25)
    assert _read("llm.device_idle", monkeypatch, {}, red) == \
        pytest.approx(100.0 * (10 - 5) / 10)


def test_llm_gmm_roofline_reads_counters_and_kernel_time(monkeypatch):
    from bench import flops, flops_llm
    tokens = 2 * flops_llm.tokens_per_step(CFG, TRAFFIC)    # two steps
    layer_steps = 2 * flops_llm.moe_layers(CFG)
    rows = 12288 * layer_steps
    table = {"spans": {}, "counters": {"feddcl.llm.expert_rows": rows,
                                       "feddcl.llm.tokens": tokens}}
    need = layer_steps * flops_llm.gmm_calls_per_layer_step()
    red = _trace(need, 0.005)
    least, bound = flops.least_seconds(
        *flops_llm.grouped_matmuls(CFG, rows, layer_steps),
        flops.peaks("TPU v5 lite"))
    got = _read("llm.gmm_roofline", monkeypatch, table, red)
    assert bound == "compute"
    assert got == pytest.approx(100.0 * least / (need * 0.005))
    # silent with fewer kernel calls than the layer-steps need, or no rows
    assert _read("llm.gmm_roofline", monkeypatch, table,
                 _trace(need - 1, 0.005)) is None
    assert _read("llm.gmm_roofline", monkeypatch,
                 {"spans": {}, "counters": {"feddcl.llm.tokens": tokens}},
                 red) is None


def test_the_readers_on_a_recorded_trace(monkeypatch):
    """A traced window of the cell on the chip (every op merged into busy
    intervals, the grouped-matmul ops one by one), with the program's
    table and the window's counters: each reader gives what the run
    printed, and the kernel's calls are found by their name."""
    rec = common.load_json(DATA / "recorded_llm_trace.json")
    red = trace_reduce.reduce(
        [trace_reduce.Span(*s) for s in rec["spans"]],
        [trace_reduce.Op(*o) for o in rec["ops"]])
    assert red.op_seconds("grouped_matmul")[1] == 384
    for name, printed in rec["printed"].items():
        got = _read(name, monkeypatch, rec["table"], red, rec["counters"])
        # merging ops less than 1 us apart hides under 0.01% of idle time
        assert got == pytest.approx(printed, rel=1e-6, abs=0.01), name
