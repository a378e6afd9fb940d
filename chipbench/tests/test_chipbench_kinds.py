"""Local models found by name: ``chipbench/models/<model_kind>.py``.

A second model kind, the paper's MNIST_MLP (``tests/data/kinds/``: its
kind, FLOP counter, configuration and mix), exists only as files in a
temporary root: it builds, runs a lap and is ``correct`` against its own
plain reference on the CPU, and no file of ``chipbench/`` is written.  The
reference part of every kind imports nothing of the program.
"""
import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, harness, reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402

KINDS_DATA = os.path.join(ROOT, "chipbench", "tests", "data", "kinds")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SEED = 2 ** 33 + 17
PEAK = {"bf16_flops_per_s": 197e12}
CELL = "paper-mnist-mlp.asyncfleo"


def _snapshot(top):
    """Every file under ``top`` but bytecode caches: size and mtime."""
    out = {}
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            st = os.stat(os.path.join(d, f))
            out[os.path.relpath(os.path.join(d, f), top)] = (st.st_size,
                                                             st.st_mtime_ns)
    return out


def _kind_root(tmp_path):
    """A checkout that holds the MLP kind's files, the commit rules and a
    ``BENCHMARK.json`` naming one cell."""
    bench_dir = tmp_path / "chipbench"
    for sub in os.listdir(KINDS_DATA):
        shutil.copytree(os.path.join(KINDS_DATA, sub), bench_dir / sub)
    shutil.copytree(os.path.join(ROOT, "chipbench", "rules"),
                    bench_dir / "rules")
    cfg = json.load(open(bench_dir / "configs" / "paper-mnist-mlp.json"))
    bench = {
        "configs": [{"name": cfg["name"], "source": cfg["source"],
                     "file": "chipbench/configs/paper-mnist-mlp.json",
                     "reduced": cfg["reduced"], "why": "MNIST_MLP"}],
        "workloads": [{"name": CELL, "config": cfg["name"],
                       "traffic": "asyncfleo-mlp", "chips": 1,
                       "why": "a model kind added as files alone"}],
        "end_to_end": BENCH["end_to_end"], "per_layer": BENCH["per_layer"]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_a_model_kind_is_new_files_only(tmp_path):
    import jax
    before = _snapshot(os.path.join(ROOT, "chipbench"))
    root = _kind_root(tmp_path)
    cell = harness.load_cell(CELL, root=root)
    assert cell.root == root
    cell.config = dict(cell.config, horizon_s=6400.0, local_iters=2)
    bench = harness.Bench(cell, SEED).build()
    assert sorted(bench.w0) == ["b1", "b2", "b3", "w1", "w2", "w3"]
    assert bench.fwd_flops == 2 * (784 * 256 + 256 * 256 + 256 * 10)
    bench.warm()
    result, _lines = bench_run.measure(bench, 0.0, False, jax.devices()[0],
                                       PEAK)
    assert result["attempted"] >= 1
    w0 = reference.initial_model(cell.config, bench.seed, root)
    ok, checks, lines = bench_run.verify(
        cell, check.host_capture(bench.capture, w0), bench.seed,
        bench.local_iters, w0=w0)
    assert ok, lines
    assert checks["sched_diff"]["value"] == 0
    assert _snapshot(os.path.join(ROOT, "chipbench")) == before


def test_a_missing_kind_is_refused(tmp_path):
    cfg = dict(harness.load_cell(BENCH["workloads"][0]["name"]).config,
               model_kind="no-such-kind")
    with pytest.raises(ValueError, match="no models file"):
        harness.run_seed(7, cfg)
    with pytest.raises(ValueError, match="no flops file"):
        harness.forward_flops(cfg, str(tmp_path))


_PROBE = r"""
import json, sys
import numpy as np
root, src, path, config = sys.argv[1:5]
sys.path[:0] = [root, src]
from chipbench import reference
kind = reference.load_module(path, "kind_under_test")
cfg = json.load(open(config))
inputs, shards = kind.data(11, cfg)
w = kind.init_params(11, cfg)
m = min(len(s) for s in shards)
sel = np.stack([shards[s][:m] for s in (0, 1)])
tree, losses = kind.train_participants(
    w, tuple(a[sel] for a in inputs), np.array([0, 1]), 5, cfg, 2, "highest")
print(json.dumps({
    "repro": sorted(k for k in sys.modules if k.startswith("repro")),
    "samples": sorted({len(a) for a in inputs}),
    "shards": len(shards), "keys": sorted(tree) == sorted(w),
    "rows": sorted({np.shape(v)[0] for v in tree.values()}),
    "losses": [float(x) for x in np.asarray(losses)]}))
"""


def _kinds():
    out = []
    for path in sorted(glob.glob(os.path.join(ROOT, "chipbench", "models",
                                              "*.py"))):
        kind = os.path.basename(path)[:-3]
        for c in BENCH["configs"]:
            cfg = os.path.join(ROOT, c["file"])
            if json.load(open(cfg))["model_kind"] == kind:
                out.append(pytest.param(path, cfg, id=c["name"]))
    for path in sorted(glob.glob(os.path.join(KINDS_DATA, "models",
                                              "*.py"))):
        kind = os.path.basename(path)[:-3]
        for cfg in sorted(glob.glob(os.path.join(KINDS_DATA, "configs",
                                                 "*.json"))):
            if json.load(open(cfg))["model_kind"] == kind:
                out.append(pytest.param(path, cfg,
                                        id=os.path.basename(cfg)[:-5]))
    return out


@pytest.mark.parametrize("path,config", _kinds())
def test_reference_part_imports_no_program(path, config):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", _PROBE, ROOT,
                        os.path.join(ROOT, "src"), path, config],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    cfg = json.load(open(config))
    assert got["repro"] == []
    assert got["samples"] == [cfg["train_samples"]]
    assert got["shards"] == (cfg["constellation"]["num_orbits"]
                             * cfg["constellation"]["sats_per_orbit"])
    assert got["keys"] and got["rows"] == [2]
    assert len(got["losses"]) == 2
    assert all(0.0 < x < 10.0 for x in got["losses"])
