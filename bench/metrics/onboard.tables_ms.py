"""Mean wall time of an admission's serving-table refresh
(`ServeCollab._refresh_tables`: every tenant's combined map rebuilt and
uploaded) over the traced window, from the program's own span."""

SPAN = "feddcl.serve.refresh_tables"


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without spans
        return None
    s = obs.snapshot()["spans"].get(SPAN)
    if not s or not s["n"]:
        return None
    return 1e3 * s["total_s"] / s["n"]
