"""Expert-parallel all-to-all MoE: single-rank equivalence + an 8-fake-device
multi-rank equivalence run in a subprocess (device count is locked at first
jax init, so the multi-rank case needs its own process)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import moe as MOE
from repro.launch.mesh import make_mesh
from repro.models.moe_ep import ep_capacity, make_ep_moe_layer


def test_ep_capacity_rounding():
    assert ep_capacity(128, 2, 4, 1.0) % 8 == 0
    assert ep_capacity(1, 1, 64, 1.0) == 8          # floor


def test_ep_single_rank_matches_reference():
    cfg = ARCHS["deepseek-v2-236b"].reduced().replace(
        dtype="float32", moe_capacity_factor=64.0)
    mesh = make_mesh((1, 1), ("data", "model"))
    key = jax.random.PRNGKey(0)
    p = MOE.init_moe_ffn(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model)) * 0.5
    out, aux = make_ep_moe_layer(cfg, mesh, capacity_factor=64.0)(p, x)
    ref = MOE.moe_ffn_reference(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-4)
    assert np.isfinite(float(aux))


MULTI_RANK_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS
    from repro.models import moe as MOE
    from repro.models.moe_ep import make_ep_moe_layer

    cfg = ARCHS["deepseek-v2-236b"].reduced().replace(
        dtype="float32", moe_capacity_factor=64.0)     # 4 experts / 4 ranks
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    p = MOE.init_moe_ffn(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model)) * 0.5
    out, aux = make_ep_moe_layer(cfg, mesh, capacity_factor=64.0)(p, x)
    ref = MOE.moe_ffn_reference(p, cfg, x)
    err = float(jnp.max(jnp.abs(out - ref)))
    print("ERR", err)
    assert err < 1e-4, err
""")


def test_ep_multi_rank_matches_reference():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MULTI_RANK_SCRIPT],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ERR" in proc.stdout
