"""wait_gap_ms_per_commit: device-idle milliseconds per window commit in
gaps of at least 50 us whose innermost program span (``asyncfleo.*``,
``program_trace.py``) is a blocking read, ``eval_read`` or
``dist_read``: the host has handed the device everything and waits, and
the device still idles.

In ``paper-cifar.asyncfleo`` the reading is mostly the profiler's cost:
while it records, the device starts each fused program about 0.6 s after
its dispatch, against at most 95 ms a commit of idle untraced (PERF.md,
section 5).  There it cannot move with the program until the traced run
records less."""
from chipbench import program_trace


def read(ctx):
    pt = program_trace.for_run(ctx)
    if pt is None or pt.offset_ns is None or not ctx["commits"]:
        return None
    return 1e3 * pt.gap_seconds(waits=True) / ctx["commits"]
