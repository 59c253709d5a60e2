"""Model FLOP/s utilization of the LLM rounds in the traced window: the
share's model operations per sequence from shapes (bench/flops_llm.py;
causal attention at S²/2, the recomputed forward not counted) times the
sequences trained per second, over the chip's bf16 peak."""
from bench import flops, flops_llm


def read(ctx):
    c = ctx["counters"]
    if not c.get("sequences"):
        return None
    rate = c["sequences"] / c["seconds"]
    pk = flops.peaks(ctx["device_kind"])
    return 100.0 * flops_llm.train_per_sequence(ctx["cfg"]) * rate / \
        pk["bf16_flops_per_s"]
