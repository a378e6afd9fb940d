"""epoch_device_ms: device milliseconds per execution of the fused epoch
program's XLA module (``jit(_trace)``) in the traced window."""

MODULE_PREFIX = "jit__trace"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    seconds, count = tr["module_time"](MODULE_PREFIX)
    if not count:
        return None
    return 1e3 * seconds / count
