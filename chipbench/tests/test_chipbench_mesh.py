"""A cell whose ``chips`` is 4 runs through a four-device mesh: in a
subprocess with four host devices forced on the CPU, a ``chips: 4`` MNIST
test cell, found in a temporary root, builds its mesh (one axis, ``"data"``),
hands it to the fused program and the simulation,
runs its bank sharded over the four devices and is ``correct``; with the
exchange between the devices left out of eq. 14 (each device's partial
contraction alone, as if the psum were missing) it is not.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "paper-mnist.asyncfleo-4chips"

SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    root, repo, cell_name = sys.argv[1:4]
    sys.path[:0] = [os.path.join(repo, "src"), root]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import check, harness, reference
    from chipbench import run as bench_run
    assert len(jax.devices()) == 4

    cell = harness.load_cell(cell_name, root=root)
    cell.config = dict(cell.config, horizon_s=6400.0, local_iters=2)
    bench = harness.Bench(cell, 2 ** 33 + 17).build()
    mesh = bench.mesh
    out = {"axes": list(mesh.axis_names), "devices": int(mesh.devices.size),
           "prog_mesh": bench.prog.mesh is mesh,
           "sim_mesh": bench.simcfg.mesh is mesh}
    bench.warm()
    ref = reference.lap(cell.config, cell.mix["spec"], bench.seed,
                        local_iters=2, root=root)

    def verdict():
        bench_run.measure(bench, 0.0, False, jax.devices()[0],
                          {"bf16_flops_per_s": 197e12})
        ok, checks, lines = bench_run.verify(
            cell, check.host_capture(bench.capture, ref.w0), bench.seed, 2,
            ref=ref)
        return ok, {k: v["value"] for k, v in checks.items()}

    out["ok"], out["nums"] = verdict()
    out["bank_devices"] = len(bench.capture.stack.sharding.device_set)

    disp = bench.prog._step
    inner = disp.fn
    ndata = mesh.devices.size

    def no_exchange(w, carry, inputs, ids, seed, wv_bank, wv_carry, base_w,
                    *rest):
        keep = w + 0.0
        new_w, stack, dists, losses = inner(w, carry, inputs, ids, seed,
                                            wv_bank, wv_carry, base_w,
                                            *rest)
        loc = stack.shape[0] // ndata
        part = jnp.dot(wv_bank[:loc], stack[:loc], precision="highest")
        new_w = base_w * keep + part + jnp.dot(wv_carry, carry,
                                               precision="highest")
        return new_w, stack, dists, losses
    disp.fn = no_exchange
    out["fault_ok"], out["fault_nums"] = verdict()
    print(json.dumps(out))
""")


def _root(tmp_path):
    """A checkout whose ``BENCHMARK.json`` names one four-chip cell over
    the MNIST configuration."""
    bench_dir = tmp_path / "chipbench"
    for sub in ("configs", "mixes", "models", "flops", "rules"):
        shutil.copytree(os.path.join(ROOT, "chipbench", sub), bench_dir / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for f in ("__init__.py", "check.py", "harness.py", "reference.py",
              "run.py", "trace_reduce.py"):
        shutil.copy(os.path.join(ROOT, "chipbench", f), bench_dir / f)
    cfg = next(c for c in BENCH["configs"] if c["name"] == "paper-mnist-cnn")
    bench = {"configs": [cfg],
             "workloads": [{"name": CELL, "config": cfg["name"],
                            "traffic": "asyncfleo-converge", "chips": 4,
                            "why": "the main layer's bank over four chips"}],
             "end_to_end": BENCH["end_to_end"],
             "per_layer": BENCH["per_layer"]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_four_chip_cell_runs_through_its_mesh(tmp_path):
    root = _root(tmp_path)
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT, root, ROOT, CELL],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["axes"] == ["data"] and got["devices"] == 4
    assert got["prog_mesh"] and got["sim_mesh"]
    assert got["bank_devices"] == 4
    assert got["ok"], got["nums"]
    assert got["nums"]["sched_diff"] == 0
    assert not got["fault_ok"]
    assert got["fault_nums"]["eq14_gap"] > 2e-5
