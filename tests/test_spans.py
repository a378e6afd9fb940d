"""The simulator's wall-clock spans and the fused program's named scopes
(``obs/span.py``, DESIGN.md §12).

Runs of the event-driven runtime under ``jax.profiler.trace`` on a tiny
image-classifier pool, read back with ``jax.profiler.ProfileData``: every
``asyncfleo.*`` span is there, one ``commit`` span per history row, the
``dispatch`` spans carry the program's participants, nested segments stay
inside their parents, and a running trace changes no result.  The fused
program counts its traces, and its lowered HLO carries the four named
scopes in metadata alone.
"""
import contextlib
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import SmallNetConfig
from repro.core import FLSimulation, SimConfig
from repro.core import epoch_step
from repro.core.constellation import WalkerDelta
from repro.core.epoch_step import SCOPES, EpochStepProgram, carry_capacity
from repro.core.modelbank import FlatSpec, pad_bucket_ids
from repro.fl import Evaluator, ImageClassifierPool, get_strategy
from repro.models import cnn
from repro.obs import SPAN_PREFIX, span, tracing

from test_epoch_step import TinyFusedTrainer, W0

CFG = SmallNetConfig("tiny", "mlp", image_size=8, channels=1,
                     num_classes=3, hidden=8)
CONST = WalkerDelta(num_orbits=2, sats_per_orbit=4)
NEVER = 1.01                      # a target never met: a read per commit

HOST_SPANS = ("run", "commit", "timing", "step", "input_gather",
              "input_put", "dispatch", "agg", "carry", "eval", "eval_read")


def _pool():
    rng = np.random.default_rng(0)
    images = rng.standard_normal((160, 8, 8, 1)).astype(np.float32)
    labels = np.asarray(rng.integers(0, 3, 160))
    shards = [np.arange(i * 20, (i + 1) * 20) for i in range(8)]
    pool = ImageClassifierPool(CFG, images, labels, shards, local_iters=2,
                               batch_size=4)
    ev = Evaluator(CFG, images[:32], labels[:32])
    w0 = jax.device_get(cnn.init_params(jax.random.PRNGKey(0), CFG))
    return pool, ev, w0


@pytest.fixture(scope="module")
def setup():
    return _pool()


def _run(setup, strategy, max_epochs=4):
    pool, ev, w0 = setup
    fls = FLSimulation(get_strategy(strategy), pool, ev,
                       SimConfig(event_driven=True, duration_s=43200.0,
                                 seed=3),
                       CONST)
    calls = []
    inner = fls._fused_commit

    def commit(prog, beta, ids_np, participants, *a, **kw):
        calls.append(len(participants))
        return inner(prog, beta, ids_np, participants, *a, **kw)
    fls._fused_commit = commit
    hist = fls.run(w0, max_epochs=max_epochs, target_accuracy=NEVER)
    return fls, hist, calls


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return out, spans


@pytest.mark.parametrize("strategy,grouped", [("asyncfleo-hap", True),
                                              ("fedasync", False)])
def test_spans_cover_an_event_driven_run(setup, tmp_path, strategy,
                                         grouped):
    (fls, hist, calls), spans = _traced(tmp_path,
                                        lambda: _run(setup, strategy))
    names = [n for n, _s, _e, _a in spans]
    expect = HOST_SPANS + (("group", "dist_read") if grouped else ())
    assert set(expect) <= set(names), sorted(set(expect) - set(names))
    assert names.count("run") == 1
    assert names.count("commit") == len(hist) == len(calls)
    # one dispatch span per training commit, carrying its participants
    dispatches = [a for n, _s, _e, a in spans if n == "dispatch"]
    trained = [c for c in calls if c]
    assert [a["participants"] for a in dispatches] == trained
    assert [a["rows"] for a in dispatches] == \
        [pad_bucket_ids(list(range(c)))[0].shape[0] for c in trained]
    n_params = fls._spec.num_params
    assert all(a["params"] == n_params for a in dispatches)
    assert all(a["carry_rows"] == carry_capacity(a["carried"])
               for a in dispatches)
    puts = [a for n, _s, _e, a in spans if n == "input_put"]
    img_bytes = 8 * 8 * 1 * 4 + 8      # a float32 image and an int64 label
    assert [a["bytes"] for a in puts] == \
        [r * 20 * img_bytes for r in (a["rows"] for a in dispatches)]
    # each commit span lies inside the run span
    (_n, r0, r1, _a), = [s for s in spans if s[0] == "run"]
    assert all(r0 <= s <= e <= r1 for n, s, e, _a in spans
               if n == "commit")
    # the nested segments' seconds stay inside their parents'
    seg = fls.segment_seconds
    assert seg["input_gather"] + seg["input_put"] + seg["dispatch"] \
        <= seg["step"]
    assert seg["eval_read"] <= seg["eval"]
    assert seg["dist_read"] <= seg["group"]
    assert (seg["dist_read"] > 0) == grouped


@pytest.mark.parametrize("strategy", ["asyncfleo-hap", "fedasync"])
def test_a_running_trace_changes_no_result(setup, tmp_path, strategy):
    def rows(hist):
        return [dataclasses.astuple(r) for r in hist]

    fls_a, hist_a, _ = _run(setup, strategy)
    (fls_b, hist_b, _), spans = _traced(tmp_path,
                                        lambda: _run(setup, strategy))
    assert spans
    assert rows(hist_a) == rows(hist_b)
    for a, b in zip(jax.tree.leaves(fls_a.global_model()),
                    jax.tree.leaves(fls_b.global_model())):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_span_is_a_no_op_without_a_trace():
    assert not tracing()
    assert span("x", participants=3) is span("y")


def _step_args(prog, rows=4, carry_rows=4, kpad=0, blocked_m=0):
    n = prog.spec.num_params
    ids = np.arange(rows, dtype=np.int32)
    return (jnp.zeros((n,), jnp.float32), jnp.zeros((carry_rows, n)),
            None, ids, 1, np.zeros(rows), np.zeros(carry_rows), 1.0,
            np.zeros(rows), np.zeros(rows, np.int32), kpad, blocked_m,
            np.zeros((kpad, carry_rows)), jnp.zeros((n,), jnp.float32))


def test_traces_rise_once_per_static_signature():
    prog = EpochStepProgram(FlatSpec.of(W0),
                            TinyFusedTrainer(W0).epoch_train_fn())
    assert prog.traces == 0
    prog.step(*_step_args(prog))
    prog.step(*_step_args(prog))
    assert (prog.traces, prog.dispatches) == (1, 2)
    prog.step(*_step_args(prog, carry_rows=8))       # new carry rows
    prog.step(*_step_args(prog, kpad=2))             # new static kpad
    prog.step(*_step_args(prog, kpad=2))
    assert (prog.traces, prog.dispatches) == (3, 5)
    prog.step(*_step_args(prog), fallback=True)      # same signature
    assert (prog.traces, prog.fallback_dispatches) == (3, 1)


def _lowered(setup):
    pool, _ev, w0 = setup
    prog = EpochStepProgram(FlatSpec.of(w0), pool.epoch_train_fn())
    n, rows, cap = prog.spec.num_params, 4, 4
    ids = np.arange(rows, dtype=np.int32)
    return prog._step.lower(
        jnp.zeros((n,), jnp.float32), jnp.zeros((cap, n), jnp.float32),
        jax.device_put(pool.epoch_inputs(ids)), jnp.asarray(ids),
        np.uint32(1), jnp.zeros(rows, jnp.float32),
        jnp.zeros(cap, jnp.float32), np.float32(1.0),
        jnp.ones(rows, jnp.float32), jnp.zeros(rows, jnp.int32), 2, 2,
        jnp.zeros((2, cap), jnp.float32), jnp.zeros((n,), jnp.float32))


def _no_metadata(hlo: str) -> str:
    """The module header and its computations, without op metadata and
    without the source-location tables that metadata points into."""
    lines = hlo.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    body = "\n".join([lines[0]] + lines[start:])
    return re.sub(r",? ?metadata=\{[^}]*\}", "", body)


def test_named_scopes_change_metadata_alone(setup, monkeypatch):
    scoped = _lowered(setup)
    text = scoped.as_text(debug_info=True)
    for name in SCOPES:
        assert f"{name}/" in text, name
    compiled = scoped.compile().as_text()
    assert "local_train/" in compiled
    # the same program traced with every named scope a no-op
    monkeypatch.setattr(epoch_step.jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    plain = _lowered(setup)
    assert "local_train/" not in plain.as_text(debug_info=True)
    assert plain.as_text() == scoped.as_text()
    assert _no_metadata(plain.compile().as_text()) == \
        _no_metadata(compiled)
