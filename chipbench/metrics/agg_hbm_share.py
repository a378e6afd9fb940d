"""agg_hbm_share: the HBM bytes eq. 14 needs over the bytes the chip's
HBM could move in the device time of the fused program's server path, the
``flatten`` and ``aggregate`` named scopes (``program_trace.py``).

Per ``asyncfleo.dispatch`` span in the window the server path reads the
real participants' trained models and the carried ones, reads the global
model and writes the new one: (participants + carried + 2) x params x 4
B; padding rows do not count.  The training loop leaves the trained
models in HBM, and ``flatten`` reads them from there into the bank; XLA
may keep that bank in on-chip memory (it does for MNIST's 64 x 206,922
bank on a v5e: memory space 1 in the compiled HLO), and the contraction
then reads it at more than HBM's rate.  So the time is the union of both
scopes' ops, the whole path that must move those bytes through HBM
(PERF.md, section 6).  Bandwidth: ``peaks.json``."""
from chipbench import program_trace


def read(ctx):
    pt = program_trace.for_run(ctx)
    if pt is None or pt.offset_ns is None:
        return None
    needed = sum((a["participants"] + a["carried"] + 2) * a["params"] * 4
                 for _n, _s, _e, a in pt.in_window("dispatch"))
    seconds = pt.scope_seconds("flatten", "aggregate")
    if not needed or not seconds:
        return None
    return 100.0 * needed / (seconds * ctx["peak"]["hbm_bytes_per_s"])
