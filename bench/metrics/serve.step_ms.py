"""Mean wall time of one ServeCollab.step() (admission, packing, dispatch,
host sync, scatter), over every step of the traced window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    return 1e3 * c["step_s"] / c["steps"]
