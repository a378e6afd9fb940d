"""The chip benchmark's data and harness, on the CPU: cells, configurations,
mixes and metric readers found by name, the FLOP counter, the peak table,
and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    cell = harness.load_cell(name)
    w = {w["name"]: w for w in BENCH["workloads"]}[name]
    assert cell.config["name"] == w["config"]
    assert cell.chips == w["chips"] == 1
    assert set(cell.mix["spec"]) >= set(harness.SPEC_KEYS)
    assert cell.mix["spec"]["ps_scenario"] in cell.config["ps"]
    assert cell.metrics("end_to_end")[0]["name"] == "setup_s"
    for m in cell.metrics("per_layer"):
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics",
                                           m["name"] + ".py"))


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_the_catalogue_names(entry):
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["limits"]) >= {"sched_diff", "row_change_gap", "eq14_gap"}
    assert cfg["limits"]["sched_diff"] == 0


@pytest.mark.parametrize("mix", sorted(os.listdir(
    os.path.join(ROOT, "chipbench", "mixes"))))
def test_mix_files_name_a_strategy(mix):
    import dataclasses
    from chipbench import reference
    from repro.fl import get_strategy
    m = json.load(open(os.path.join(ROOT, "chipbench", "mixes", mix)))
    spec = dataclasses.replace(get_strategy(m["strategy"]), **m["spec"])
    assert spec.agg_mode == m["spec"]["agg_mode"]
    reference.load_rule(m["spec"]["agg_mode"])
    assert set(m) == {"strategy", "target", "spec"}


@pytest.mark.parametrize("agg_mode", ["asyncfleo", "per_arrival"])
@pytest.mark.parametrize("case", range(6))
def test_rule_weights_match_the_program(agg_mode, case):
    """The reference's commit rule against the program's weight vector on
    commits with fresh and stale models, grouped and not."""
    from chipbench import reference
    from repro.core.aggregation import SatelliteMeta, epoch_weight_vector
    rng = np.random.default_rng(case)
    beta = int(rng.integers(1, 9))
    n = int(rng.integers(1, 12))
    sizes = rng.integers(90, 110, n)
    epochs = [int(e) for e in rng.integers(0, beta + 1, n)]
    orbit = rng.integers(0, 5, n)
    gid = {o: i % 3 for i, o in enumerate(rng.permutation(5))}
    groups = {}
    for i in range(n):
        groups.setdefault(gid[orbit[i]], []).append(i)
    metas = [SatelliteMeta(i, float(sizes[i]), (0.0, 0.0), 0.0, epochs[i])
             for i in range(n)]
    ws, base_w, info = epoch_weight_vector(agg_mode, metas, beta, groups)
    rule = reference.load_rule(agg_mode)
    rws, rbase, gamma, stale_groups = rule.weights(
        [(i, float(sizes[i]), epochs[i]) for i in range(n)], beta,
        list(groups.values()))
    np.testing.assert_allclose(rws, ws, rtol=1e-12, atol=1e-15)
    assert abs(rbase - base_w) < 1e-12
    assert abs(gamma - info["gamma"]) < 1e-12
    assert stale_groups == info["stale_groups"]


@pytest.mark.parametrize("config,macs", [("paper-mnist-cnn", 1_218_048),
                                         ("paper-cifar-cnn", 6_128_896)])
def test_flop_counter_matches_the_hand_count(config, macs):
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      config + ".json")))
    mod = harness.load_module(os.path.join(ROOT, "chipbench", "flops",
                                           "cnn.py"), "flops_cnn")
    assert mod.forward_macs(cfg) == macs
    assert harness.forward_flops(cfg) == 2 * macs


@pytest.mark.parametrize("config", ["paper-mnist-cnn", "paper-cifar-cnn"])
def test_config_matches_the_program_model(config):
    from repro import configs
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      config + ".json")))
    model = getattr(configs, cfg["model"])
    assert (model.image_size, model.channels, list(model.conv_channels),
            model.hidden) == (cfg["image_size"], cfg["channels"],
                              cfg["conv_channels"], cfg["hidden"])
    s, c1, c2 = cfg["image_size"], *cfg["conv_channels"]
    params = (9 * cfg["channels"] * c1 + c1 + 9 * c1 * c2 + c2
              + (s // 4) ** 2 * c2 * cfg["hidden"] + cfg["hidden"]
              + cfg["hidden"] * 10 + 10)
    assert params == cfg["params"]


def test_peak_table():
    peaks = harness.load_peaks()
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2 ** 30
    assert "TPU v5e" in v5e["source"]


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devs,ok", [
    ([_Dev("tpu", "TPU v5 lite")], True),
    ([_Dev("cpu", "cpu")], False),
    ([_Dev("tpu", "TPU v9 imaginary")], False),
])
def test_check_device(monkeypatch, devs, ok):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: devs)
    if ok:
        assert harness.check_device(1, harness.load_peaks()) is devs[0]
    else:
        with pytest.raises(harness.NoChip):
            harness.check_device(1, harness.load_peaks())


def test_check_device_counts_chips(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Dev("tpu", "TPU v5 lite")])
    with pytest.raises(harness.NoChip):
        harness.check_device(4, harness.load_peaks())


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 5, 2 ** 40 + 3])
def test_run_seed_fits_the_program(seed):
    from repro.data import class_conditional_images, paper_noniid_partition
    cfg = harness.load_cell(CELLS[0]).config
    rs = harness.run_seed(seed, cfg)
    assert rs == harness.run_seed(seed, cfg)
    assert 0 <= rs * 1000 + 999 < 2 ** 32
    _x, labels = class_conditional_images(rs, cfg["train_samples"], size=4)
    orbits = np.arange(40) // 8
    shards = paper_noniid_partition(labels, orbits, rs)
    assert min(len(s) for s in shards) == cfg["shard_rows"]


def test_run_seeds_differ():
    cfg = harness.load_cell(CELLS[0]).config
    assert len({harness.run_seed(s, cfg) for s in range(20)}) == 20


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_run_refuses_a_cpu_device():
    p = _run(["--workload", CELLS[0], "--seed", "7", "--seconds", "1"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_run_needs_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", CELLS[0], "--seed", "7", "--seconds", "1"],
             tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
