"""The reduction from trace intervals to device numbers, the operation and
byte counts, and the per-layer readers, on hand-computed cases and on a
small recorded trace.

    PYTHONPATH=src python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import math
import pathlib

import pytest

from bench import flops, trace_reduce
from bench.trace_reduce import Op, Span

DATA = pathlib.Path(__file__).parent / "data"


def _ops(chip, *ivals, label="fusion.1"):
    return [Op(chip, label, s, e) for s, e in ivals]


def test_union_and_gaps():
    u = trace_reduce.union([(5, 8), (0, 2), (1, 3), (7, 9), (20, 30)], 0, 25)
    assert u == [(0, 3), (5, 9), (20, 25)]
    assert trace_reduce.gaps_of(u, 0, 25) == [(3, 5), (9, 20)]
    assert trace_reduce.gaps_of([], 2, 4) == [(2, 4)]


def test_busy_is_the_union_and_gaps_go_to_the_innermost_span():
    spans = [Span("bench.window", 0, 1000), Span("bench.step", 100, 600),
             Span("bench.submit", 150, 250), Span("bench.step", 700, 900)]
    ops = _ops("/device:TPU:0", (0, 100), (50, 120), (600, 700), (950, 1100))
    red = trace_reduce.reduce(spans, ops)
    # busy: [0,120] + [600,700] + [950,1000] = 270 ns of a 1000 ns window
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(270e-9)
    assert red.idle_share == pytest.approx(0.73)
    # gaps: [120,600] mid 360 -> bench.step; [700,950] mid 825 -> bench.step
    assert red.gap_s == pytest.approx({"bench.step": 730e-9})
    # op time clipped to the window: 100 + 70 + 100 + 50
    assert red.op_s["fusion.1"] == pytest.approx(320e-9)
    assert red.op_n["fusion.1"] == 4
    assert red.span_n == {"bench.step": 2, "bench.submit": 1}


def test_gap_outside_every_span_and_two_chips_average():
    spans = [Span("bench.window", 0, 100)]
    ops = (_ops("/device:TPU:0", (0, 50)) +
           _ops("/device:TPU:1", (0, 100), label="gram_cross"))
    red = trace_reduce.reduce(spans, ops)
    assert red.busy_s == pytest.approx(75e-9)
    assert red.gap_s == pytest.approx({"outside spans": 25e-9})
    assert red.op_seconds("gram_cross") == (pytest.approx(100e-9), 1)


def test_reduce_refuses_a_trace_without_window_or_device_ops():
    with pytest.raises(ValueError):
        trace_reduce.reduce([], _ops("/device:TPU:0", (0, 1)))
    with pytest.raises(ValueError):
        trace_reduce.reduce([Span("bench.window", 0, 1)], [])


def test_recorded_trace():
    """Intervals recorded from a traced run on a TPU v5 lite: the reduction
    gives the numbers written beside them."""
    rec = json.loads((DATA / "recorded_trace.json").read_text())
    spans = [Span(*s) for s in rec["spans"]]
    ops = [Op(*o) for o in rec["ops"]]
    red = trace_reduce.reduce(spans, ops)
    want = rec["reduced"]
    assert red.window_s == pytest.approx(want["window_s"])
    assert red.busy_s == pytest.approx(want["busy_s"])
    assert red.breakdown()["device_ops"][0][0] == want["top_op"]


def test_gram_counts_by_hand():
    # (5, 2000, 200): 2·5·2000·200·200 flops; reads 5·2000·200 f32, writes
    # 5·200·200 f32
    f, b = flops.gram(5, 2000, 200)
    assert f == 8.0e8 and b == 4 * (2_000_000 + 200_000)
    pk = flops.peaks("TPU v5 lite")
    t, bound = flops.least_seconds(f, b, pk)
    assert bound == "memory" and t == pytest.approx(8.8e6 / 819e9)
    t, bound = flops.least_seconds(*flops.gram(1, 2000, 250), pk)
    assert bound == "memory" and t == pytest.approx(4 * 562_500 / 819e9)


def test_protocol_grams_of_a_config():
    cfg = json.loads((DATA.parent.parent / "configs" / "har_d5c4.json")
                     .read_text())
    assert flops.protocol_grams(cfg) == [(5, 2000, 200), (1, 2000, 250)]
    assert flops.protocol_build(cfg) > sum(
        flops.gram(*s)[0] for s in flops.protocol_grams(cfg))


def test_mlp_operations_per_sample():
    # the paper's MNIST network on m̂ = 50: 76,610 parameters
    assert flops.mlp_train_per_sample([50, 500, 100, 10]) == 6 * 76_610


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")


def _reader(name):
    from bench.run import load_reader
    return load_reader(name)


def test_readers():
    red = trace_reduce.Reduced(window_s=2.0, busy_s=0.5, chips=1,
                               op_s={"custom-call.3 gram_cross": 1e-3},
                               op_n={"custom-call.3 gram_cross": 20})
    cfg = json.loads((DATA.parent.parent / "configs" / "har_d5c4.json")
                     .read_text())
    ctx = {"trace": red, "cfg": cfg, "device_kind": "TPU v5 lite",
           "counters": {"builds": 10, "seconds": 2.0, "steps": 4,
                        "step_s": 0.002, "rows_served": 100}}
    assert _reader("protocol.device_idle")(ctx) == pytest.approx(75.0)
    assert _reader("serve.step_ms")(ctx) == pytest.approx(0.5)
    assert _reader("serve.rows_per_step")(ctx) == pytest.approx(25.0)
    pk = flops.peaks("TPU v5 lite")
    least = sum(flops.least_seconds(*flops.gram(*s), pk)[0]
                for s in flops.protocol_grams(cfg))
    assert _reader("protocol.gram_roofline")(ctx) == pytest.approx(
        100 * 10 * least / 1e-3)
    assert _reader("protocol.mfu")(ctx) == pytest.approx(
        100 * flops.protocol_build(cfg) * 5 / 197e12)
    # fewer kernel calls than the builds needed: the roofline is silent
    ctx["counters"]["builds"] = 11
    assert _reader("protocol.gram_roofline")(ctx) is None
    fl = {"trace": red, "device_kind": "TPU v5 lite",
          "cfg": json.loads((DATA.parent.parent / "configs" /
                             "mnist_d5c4.json").read_text()),
          "counters": {"samples": 1.6e6, "seconds": 0.5}}
    assert _reader("fl.mfu")(fl) == pytest.approx(
        100 * 6 * 76_610 * 3.2e6 / 197e12)
    assert _reader("fl.mfu")({**fl, "counters": {}}) is None
    assert math.isfinite(_reader("fl.device_idle")(fl))
