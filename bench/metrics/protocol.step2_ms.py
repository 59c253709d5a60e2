"""Mean wall time of a build's step 2 (every user's private map and
intermediate representations, host NumPy) over the traced window, from
the program's own span."""

SPAN = "feddcl.protocol.step2"


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without spans
        return None
    s = obs.snapshot()["spans"].get(SPAN)
    if not s or not s["n"]:
        return None
    return 1e3 * s["total_s"] / s["n"]
