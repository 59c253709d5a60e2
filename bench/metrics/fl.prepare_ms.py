"""Mean wall time of the host side of an FL phase before its compiled plan
runs (padding, schedules, plan-cache lookup, upload of the padded stack)
over the traced window, from the program's own span."""

SPAN = "feddcl.fl.prepare"


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without spans
        return None
    s = obs.snapshot()["spans"].get(SPAN)
    if not s or not s["n"]:
        return None
    return 1e3 * s["total_s"] / s["n"]
