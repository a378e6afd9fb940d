"""train_device_ms: device milliseconds per execution of the fused epoch
program in the ``local_train`` named scope: the union of the intervals of
the ops charged to it (``program_trace.py``), over the executions of the
``jit__trace`` module in the traced window."""
from chipbench import program_trace


def read(ctx):
    pt = program_trace.for_run(ctx)
    if pt is None or pt.offset_ns is None:
        return None
    execs = len(pt.window_executions())
    if not execs or not any(o.scopes for o in pt.ops):
        return None
    return 1e3 * pt.scope_seconds("local_train") / execs
