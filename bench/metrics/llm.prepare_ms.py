"""Mean wall time of the host side of an LLM round before its dispatch
(building the round's token batches and uploading them) over the traced
window, from the program's own span."""

SPAN = "feddcl.llm.prepare"


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without spans
        return None
    s = obs.snapshot()["spans"].get(SPAN)
    if not s or not s["n"]:
        return None
    return 1e3 * s["total_s"] / s["n"]
