"""The launcher's per-run setup and the compile-cache helper."""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.checkpoint import load_server_state, save_server_state
from repro.compile_cache import CHECKOUT_CACHE_DIR, configure_compile_cache
from repro.configs import MNIST_CNN
from repro.core import SimConfig
from repro.core.modelbank import FlatSpec
from repro.fl import get_strategy
from repro.launch.fl_train import build_run

TINY_CNN = dataclasses.replace(MNIST_CNN, conv_channels=(2, 4), hidden=8)


def test_build_run_trains_and_keeps_the_global_model(tmp_path):
    sim, w0 = build_run(TINY_CNN, get_strategy("asyncfleo-hap"),
                        SimConfig(event_driven=True), local_iters=1)
    assert sim.constellation.num_sats == 40
    hist = sim.run(w0, max_epochs=1)
    assert len(hist) == 1 and np.isfinite(hist[0].accuracy)
    final = jax.device_get(sim.global_model())
    spec = FlatSpec.of(w0)
    assert np.max(np.abs(np.asarray(spec.flatten(final))
                         - np.asarray(spec.flatten(w0)))) > 0
    path = str(tmp_path / "server.npz")
    save_server_state(path, global_model=final, epoch=hist[-1].epoch,
                      grouping=sim.grouping.groups)
    loaded, side = load_server_state(path)
    assert side["epoch"] == 0
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(final)):
        np.testing.assert_array_equal(a, b)


def test_global_model_needs_a_model_bank_run():
    sim, _w0 = build_run(TINY_CNN, get_strategy("asyncfleo-hap"),
                         SimConfig(), local_iters=1)
    with pytest.raises(ValueError):
        sim.global_model()


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == CHECKOUT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CHECKOUT_CACHE_DIR == os.path.join(root, ".jax_cache")
