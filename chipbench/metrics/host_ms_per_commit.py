"""host_ms_per_commit: window wall milliseconds less the time the host
waits on the device, per commit: host time spent on the event runtime,
the contact plan and the commit's bookkeeping.  The host waits in the
evaluator's reads (``segment_seconds["eval"]``) and inside the fused
program's dispatches (``dispatch_s``).  Only cells that read every
commit's accuracy list it: where accuracy is read at a lap's end the host
runs ahead of the device and waits wherever the device holds it back,
so no span marks its waits."""


def read(ctx):
    if not ctx["commits"]:
        return None
    busy = (ctx["wall_s"] - ctx["segments"].get("eval", 0.0)
            - ctx["dispatch_s"])
    return 1e3 * busy / ctx["commits"]
