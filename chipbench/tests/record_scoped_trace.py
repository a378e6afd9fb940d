"""Records the small scoped chip trace that ``test_program_trace.py`` reads.

    python3 chipbench/tests/record_scoped_trace.py <out.xplane.pb>

Run on a TPU.  A jitted probe named like the fused epoch program
(``_trace``, so its module is ``jit__trace``) runs its ops in two named
scopes, ``local_train`` and ``aggregate``.  Inside a ``chipbench.window``
span and an ``asyncfleo.run`` span, three commits each

* sleep 5 ms in ``asyncfleo.timing`` (host work: the device idles),
* dispatch the probe in ``asyncfleo.dispatch``, with the arguments the
  simulator gives that span,
* wait for its result and sleep 20 ms in ``asyncfleo.eval_read`` (the
  host waits: the device idles again),

so the device idles 5 ms under host work before the first dispatch, and
then 25 ms after each execution, a gap whose midpoint lies in
``eval_read``.  The spans come from the program's own ``repro.obs.span``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.obs import span  # noqa: E402

N = 1024
COMMITS = 3
HOST_S = 0.005
WAIT_S = 0.02


def _trace(x, w):
    with jax.named_scope("local_train"):
        for _ in range(4):
            x = jnp.tanh(x @ x)
    with jax.named_scope("aggregate"):
        w = 0.5 * w + 0.5 * jnp.sum(x, axis=0)
    return x, w


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 2
    step = jax.jit(_trace)
    x = jnp.ones((N, N), jnp.float32) / N
    w = jnp.zeros((N,), jnp.float32)
    jax.block_until_ready(step(x, w))             # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"), span("run"):
        for epoch in range(COMMITS):
            with span("commit", epoch=epoch, used=N, late=0,
                      participants=N):
                with span("timing"):
                    time.sleep(HOST_S)
                with span("dispatch", participants=N, rows=N, carried=0,
                          carry_rows=4, params=N, fallback=False):
                    x, w = step(x, w)
                with span("eval_read"):
                    jax.block_until_ready((x, w))
                    time.sleep(WAIT_S)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
