"""dispatches_per_commit: the fused program's dispatches plus its fallback
dispatches over the window, per commit (the program's own counters)."""


def read(ctx):
    if not ctx["commits"]:
        return None
    return ctx["dispatches"] / ctx["commits"]
