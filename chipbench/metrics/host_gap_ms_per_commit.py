"""host_gap_ms_per_commit: device-idle milliseconds per window commit in
gaps of at least 50 us whose innermost program span (``asyncfleo.*``,
``program_trace.py``) is host work: any span but the blocking reads
``eval_read`` and ``dist_read``.  Gaps under no program span (the
harness's own work between laps) are left out."""
from chipbench import program_trace


def read(ctx):
    pt = program_trace.for_run(ctx)
    if pt is None or pt.offset_ns is None or not ctx["commits"]:
        return None
    return 1e3 * pt.gap_seconds(waits=False) / ctx["commits"]
