"""timing_ms_per_commit: host milliseconds in contact-plan queries
(``FLSimulation.segment_seconds["timing"]``) over the window, per commit."""


def read(ctx):
    if not ctx["commits"]:
        return None
    return 1e3 * ctx["segments"].get("timing", 0.0) / ctx["commits"]
