"""Records the small chip trace that ``test_chipbench_trace.py`` reads.

    python3 chipbench/tests/record_trace.py <out.xplane.pb>

Run on a TPU.  Inside a ``chipbench.window`` span it dispatches one jitted
program three times, each time in a ``chipbench.dispatch`` span and waiting
on its result, then sleeps 20 ms in a ``chipbench.eval_read`` span, so the
device is idle for at least 60 ms of the window, in gaps the host spent in
``chipbench.eval_read``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def probe_step(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    probe_step(x).block_until_ready()           # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                x = probe_step(x)
                x.block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.eval_read"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
