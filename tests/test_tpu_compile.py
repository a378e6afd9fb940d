"""Compile the main path for a described TPU v5e (2x2) with
``interpret=False``: what Mosaic or the TPU compiler would refuse (tiling,
VMEM, partitioning) fails here with no chip attached.  Nothing runs.

The topology is described only inside the ``topo`` fixture: one process at
a time may load the TPU library, so describing it at import would break
every other test worker.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import MNIST_CNN
from repro.core.epoch_step import EpochStepProgram
from repro.core.modelbank import FlatSpec
from repro.fl import ImageClassifierPool
from repro.kernels.chunk_scan.kernel import chunk_scan_flat
from repro.kernels.fed_agg.kernel import fed_agg_flat
from repro.kernels.flash_attention.kernel import flash_attention_flat
from repro.kernels.pairwise_dist.kernel import pairwise_dist_sq
from repro.launch.mesh import make_mesh
from repro.models import cnn

# the S=40 paper participants, their own row bucket (modelbank.bucket_rows),
# and a 64-row bank, as 64 participants (8 orbits of 8) would train
BANK_ROWS = (40, 64)
PAPER_PARAMS = 206_922    # MNIST_CNN at its published widths
CARRY_ROWS = 4
NEW_ORBITS = 8            # kpad: the paper's 5 orbits, a power-of-two bucket
SHARD = 100               # images per satellite (4000 over 40)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described chip's executables cannot be read back: keep them out of
    # the persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", BANK_ROWS)
def test_fed_agg_compiles_at_paper_bank(one_chip, rows):
    f32 = jnp.float32
    compiled = fed_agg_flat.lower(
        _sds((rows, PAPER_PARAMS), f32, one_chip),
        _sds((rows,), f32, one_chip), _sds((PAPER_PARAMS,), f32, one_chip),
        _sds((), f32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pairwise_dist_compiles_at_orbit_partials(one_chip):
    compiled = pairwise_dist_sq.lower(
        _sds((9, PAPER_PARAMS), jnp.float32, one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 512)])
def test_flash_attention_compiles(one_chip, causal, window):
    qkv = [_sds((32, 2048, 128), jnp.bfloat16, one_chip)] * 3
    compiled = flash_attention_flat.lower(
        *qkv, causal=causal, window=window, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("include_current", [True, False])
def test_chunk_scan_compiles(one_chip, include_current):
    bh, t, k = 64, 1024, 64
    seq = _sds((bh, t, k), jnp.float32, one_chip)
    compiled = chunk_scan_flat.lower(
        seq, seq, seq, seq, _sds((bh, k, k), jnp.float32, one_chip),
        _sds((bh, k), jnp.float32, one_chip),
        include_current=include_current, chunk=64, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _paper_program(mesh=None, use_kernel=False):
    """The fused epoch program of the paper run (MNIST_CNN, S=40), built
    from shapes: the pool's data is never gathered."""
    cfg = MNIST_CNN
    params = jax.eval_shape(lambda: cnn.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    spec = FlatSpec.of(params)
    assert spec.num_params == PAPER_PARAMS
    pool = ImageClassifierPool(cfg, np.zeros((1, 28, 28, 1), np.float32),
                               np.zeros(1, np.int32),
                               [np.arange(SHARD)] * 40)
    return EpochStepProgram(spec, pool.epoch_train_fn(), mesh=mesh,
                            use_kernel=use_kernel)


def _epoch_args(sharding, c):
    f32, i32 = jnp.float32, jnp.int32
    n = PAPER_PARAMS
    return (_sds((n,), f32, sharding), _sds((CARRY_ROWS, n), f32, sharding),
            (_sds((c, SHARD, 28, 28, 1), f32, sharding),
             _sds((c, SHARD), i32, sharding)),
            _sds((c,), i32, sharding), _sds((), jnp.uint32, sharding),
            _sds((c,), f32, sharding), _sds((CARRY_ROWS,), f32, sharding),
            _sds((), f32, sharding), _sds((c,), f32, sharding),
            _sds((c,), i32, sharding), NEW_ORBITS, 0,
            _sds((NEW_ORBITS, CARRY_ROWS), f32, sharding),
            _sds((n,), f32, sharding))


def _assert_xla_convolutions(hlo: str, rows: int):
    """The CNN's 3x3 convs are XLA convolutions (``models/cnn._conv``'s
    TPU branch): no nine-tap im2col patches, no branch left at run time."""
    assert any(" convolution(" in line and "window={size=3x3" in line
               for line in hlo.splitlines())
    assert not re.search(r"\[[0-9,]*,9,(1|16)\]", hlo)  # the 9-tap axis
    assert f"[{rows},32,28,28,1,1]" not in hlo  # conv1's shifted slices
    assert " conditional(" not in hlo


@pytest.mark.parametrize("rows", BANK_ROWS)
def test_fused_epoch_program_runs_xla_convolutions(one_chip, rows):
    prog = _paper_program()
    _assert_xla_convolutions(
        prog._step.lower(*_epoch_args(one_chip, rows)).compile().as_text(),
        rows)


@pytest.mark.parametrize("rows", BANK_ROWS)
def test_fused_epoch_program_compiles_with_fed_agg(one_chip, monkeypatch,
                                                   rows):
    # the kernel picks interpret mode from the attached platform (CPU
    # here); the described chip needs the compiled kernel
    monkeypatch.setattr("repro.kernels.fed_agg.ops.default_interpret",
                        lambda: False)
    prog = _paper_program(use_kernel=True)
    compiled = prog._step.lower(*_epoch_args(one_chip, rows)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", BANK_ROWS)
def test_fused_epoch_program_compiles_on_4_chip_mesh(topo, rows):
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    prog = _paper_program(mesh=mesh)
    compiled = prog._step.lower(
        *_epoch_args(NamedSharding(mesh, P()), rows)).compile()
    text = compiled.as_text()
    assert "all-reduce" in text                 # the bank contraction psum
    _assert_xla_convolutions(text, rows)
