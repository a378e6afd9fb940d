"""Observability subsystem (repro/obs, DESIGN.md §12).

Covers: Tracer span/instant bookkeeping and the NullTracer off-switch,
the bounded deterministic Histogram, the MetricRegistry-backed
StatsView compat layer (key-for-key against the registry snapshot),
``contention_stats()`` on a fresh runtime, the pinned ``tracer=None``
bit-parity contract, Chrome trace-event export + validation, and JSONL
round-tripping through the trace_report CLI loader.  The wall-clock
``asyncfleo.*`` spans are covered in ``test_spans.py``.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import FLSimulation, SimConfig
from repro.core.links import LinkModel
from repro.fl import get_strategy
from repro.obs import (NULL_TRACER, Histogram, MetricRegistry, StatsView,
                       Tracer, add_runtime_tracks, export_chrome,
                       export_jsonl, validate_chrome_trace)
from repro.obs.trace import (EV_COMMIT, EV_DISPATCH, EV_TRANSFER_RETRY,
                             EV_TRIGGER, SPAN_CHANNEL, SPAN_OUTAGE,
                             SPAN_ROUND)
from repro.sched import EventDrivenRuntime, FaultModel

from benchmarks.trace_report import (load_trace, ps_utilization,
                                     retry_report, round_waterfall)
from test_epoch_step import TinyFusedTrainer, W0

SIMKW = dict(duration_s=86400.0, train_time_s=300.0,
             use_model_bank=True, use_fused_step=True)
PIPE = dict(max_in_flight=3, handoff_policy="next_contact")


def _sim(name, *, spec_kw=None, **kw):
    cfg = SimConfig(event_driven=True, **{**SIMKW, **kw})
    spec = get_strategy(name)
    if spec_kw:
        spec = dataclasses.replace(spec, **spec_kw)
    return FLSimulation(spec, TinyFusedTrainer(W0), None, cfg)


def _rows(hist):
    return [(r.epoch, r.time_s, r.num_models, r.gamma, r.stale_groups)
            for r in hist]


# ---- Tracer / NullTracer ----------------------------------------------------

def test_tracer_span_lifecycle():
    t = Tracer()
    h = t.begin("round", 10.0, track="round 0", source=1)
    t.instant("MODEL_ARRIVAL", 12.0, track="round 0", sat=3)
    t.end(h, 20.0, committed=True)
    t.span("recruit", 10.0, 11.0, track="round 0")
    assert len(t.spans) == 2 and len(t.instants) == 1
    s = t.spans[0]
    assert (s.name, s.t_start, s.t_end) == ("round", 10.0, 20.0)
    assert s.args == {"source": 1, "committed": True}
    assert s.duration == 10.0
    # track order is first-appearance; unknown handle / clamp are benign
    assert t.tracks() == ["round 0"]
    t.end(999, 5.0)
    h2 = t.begin("x", 50.0)
    t.end(h2, 40.0)                       # t_end clamped to t_start
    assert t.spans[-1].t_end == 50.0


def test_tracer_close_open_spans():
    t = Tracer()
    t.begin("round", 0.0, track="round 0")
    t.begin("round", 5.0, track="round 1")
    t.close_open_spans(30.0)
    assert [s.t_end for s in t.spans] == [30.0, 30.0]
    assert t.tracks() == ["round 0", "round 1"]
    t.clear()
    assert not t.spans and not t.instants and not t.tracks()


def test_null_tracer_is_inert():
    nt = NULL_TRACER
    assert nt.enabled is False
    h = nt.begin("round", 0.0, track="round 0", junk=1)
    assert h == -1
    nt.end(h, 1.0)
    nt.instant("x", 2.0)
    nt.span("y", 0.0, 1.0)
    nt.close_open_spans(3.0)
    assert not hasattr(nt, "spans")       # __slots__: no buffers at all


# ---- Histogram / MetricRegistry / StatsView ---------------------------------

def test_histogram_bounded_with_exact_aggregates():
    h = Histogram("w", max_samples=64)
    n = 10_000
    for i in range(n):
        h.observe(float(i))
    assert len(h.samples) <= 64           # decimated, never unbounded
    s = h.summary()
    assert s["count"] == n                # aggregates stay exact
    assert s["sum"] == pytest.approx(n * (n - 1) / 2)
    assert (s["min"], s["max"]) == (0.0, float(n - 1))
    # percentiles come from the retained (stride-decimated) sample set:
    # uniform data keeps them within a stride of the true quantile
    assert s["p50"] == pytest.approx(n / 2, rel=0.05)
    assert s["p95"] == pytest.approx(0.95 * n, rel=0.05)
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_histogram_empty_and_validation():
    h = Histogram("w")
    assert h.summary() == {"count": 0, "sum": 0.0, "min": None,
                           "max": None, "p50": None, "p95": None,
                           "p99": None}
    assert h.percentile(50.0) is None
    with pytest.raises(ValueError):
        Histogram("w", max_samples=1)


def test_stats_view_is_a_mutable_mapping_over_the_registry():
    reg = MetricRegistry()
    st = StatsView(reg, counter_keys=("a", "b"), histogram_keys=("h",))
    assert st["a"] == 0 and "a" in st
    st["a"] += 2
    st["b"] = 5
    assert reg.counter("a").value == 2.0 and st["b"] == 5
    assert isinstance(st["a"], int)       # integer counters render as int
    reg.observe("h", 1.5)
    assert st["h"]["count"] == 1          # histogram key -> summary dict
    with pytest.raises(TypeError):
        st["h"] = []                      # histograms are not assignable
    with pytest.raises(TypeError):
        del st["a"]
    st["new_key"] = 3                     # unknown keys become counters
    assert reg.counter("new_key").value == 3.0
    assert set(dict(st)) == {"a", "b", "h", "new_key"}


def test_runtime_stats_view_matches_registry_snapshot():
    """satellite (c): the compat dict and the registry are one store —
    every key the view exposes reads back the registry's value."""
    fm = FaultModel(loss_prob=0.3, max_retries=2, adaptive_backoff=True)
    fls = _sim("asyncfleo-twohap", fault_model=fm, spec_kw=PIPE)
    rt = EventDrivenRuntime(fls)
    rt.run(W0, max_epochs=5)
    st = dict(rt.stats)
    assert st["transfers_failed"] > 0
    assert st["backoff_delays_s"]["count"] == st["transfer_retries"]
    for key, val in st.items():
        assert rt.metrics.get(key) == val
    assert rt.stats.registry is rt.metrics


def test_contention_stats_on_fresh_runtime():
    """satellite (c): telemetry is well-formed before any event runs —
    zero grants, empty queue-wait histogram — and None without a model."""
    fls = _sim("asyncfleo-twohap", spec_kw=dict(ps_channels=4))
    rt = EventDrivenRuntime(fls)          # no run()
    st = rt.contention_stats()
    assert st["ps_channels"] == 4
    for side in ("tx", "rx"):
        assert st[side]["grants"] == 0
        assert st[side]["queue_wait_s"] == 0.0
        assert st[side]["queue_wait_hist"]["count"] == 0
        assert st[side]["queue_wait_hist"]["p95"] is None
    bare = EventDrivenRuntime(_sim("asyncfleo-twohap"))
    assert bare.contention_stats() is None


# ---- tracer=None bit-parity (pinned) ----------------------------------------

def test_null_tracer_bit_parity_pinned():
    """The §12 off-switch contract: a traced run and a tracer=None run
    of the same contended, faulty, pipelined scenario produce
    bit-identical histories and final weights."""
    fm = FaultModel(loss_prob=0.3, max_retries=2)
    kw = dict(fault_model=fm, link=LinkModel(rate_bps=10.0))
    sk = {**PIPE, "ps_channels": 1}
    plain = _sim("asyncfleo-twohap", spec_kw=sk, **kw)
    traced = _sim("asyncfleo-twohap", tracer=Tracer(), spec_kw=sk, **kw)
    rt_p = EventDrivenRuntime(plain)
    rt_t = EventDrivenRuntime(traced)
    hp = rt_p.run(W0, max_epochs=6)
    ht = rt_t.run(W0, max_epochs=6)
    assert _rows(hp) == _rows(ht)
    assert (np.asarray(plain._w_flat).tobytes()
            == np.asarray(traced._w_flat).tobytes())
    assert dict(rt_p.stats) == dict(rt_t.stats)
    assert rt_p.tracer is NULL_TRACER and not rt_p.tracer.enabled
    assert len(rt_t.tracer.spans) > 0


# ---- traced run -> Chrome export -> report ----------------------------------

def _traced_run(max_epochs=5):
    fm = FaultModel(loss_prob=0.3, max_retries=2, ps_outage_fraction=0.1)
    fls = _sim("asyncfleo-twohap", tracer=Tracer(), fault_model=fm,
               link=LinkModel(rate_bps=10.0),
               spec_kw={**PIPE, "ps_channels": 1})
    rt = EventDrivenRuntime(fls)
    hist = rt.run(W0, max_epochs=max_epochs)
    return fls, rt, hist


def test_traced_run_exports_valid_chrome_trace(tmp_path):
    fls, rt, hist = _traced_run()
    tracer = rt.tracer
    round_spans = [s for s in tracer.spans if s.name == SPAN_ROUND]
    assert len(round_spans) >= len(hist)  # >=1 round span per epoch
    for name in (EV_TRIGGER, EV_DISPATCH, EV_COMMIT):
        assert sum(i.name == name for i in tracer.instants) >= len(hist)
    assert any(i.name == EV_TRANSFER_RETRY for i in tracer.instants)
    add_runtime_tracks(tracer, rt)
    assert any(s.name == SPAN_CHANNEL for s in tracer.spans)
    assert any(s.name == SPAN_OUTAGE for s in tracer.spans)

    path = tmp_path / "trace.json"
    obj = export_chrome(tracer, str(path))
    assert validate_chrome_trace(obj) == []
    assert validate_chrome_trace(json.loads(path.read_text())) == []
    # ps tracks come first in the pid/tid layout, then rounds in order
    names = [e["args"]["name"] for e in obj["traceEvents"]
             if e.get("ph") == "M"]
    ps = [n for n in names if n.startswith("ps ")]
    assert names[:len(ps)] == sorted(ps)


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": {}}) != []
    bad_ph = {"traceEvents": [{"ph": "Z", "name": "x", "pid": 0,
                               "tid": 0, "ts": 0.0}]}
    assert any("ph" in e for e in validate_chrome_trace(bad_ph))
    neg_dur = {"traceEvents": [{"ph": "X", "name": "x", "pid": 0,
                                "tid": 0, "ts": 0.0, "dur": -1.0}]}
    assert validate_chrome_trace(neg_dur) != []


def test_jsonl_and_chrome_roundtrip_through_trace_report(tmp_path):
    fls, rt, hist = _traced_run()
    add_runtime_tracks(rt.tracer, rt)
    jpath, cpath = tmp_path / "t.jsonl", tmp_path / "t.json"
    n = export_jsonl(rt.tracer, str(jpath))
    export_chrome(rt.tracer, str(cpath))
    assert n == len(rt.tracer.spans) + len(rt.tracer.instants)
    a, b = load_trace(str(cpath)), load_trace(str(jpath))
    for t in (a, b):
        assert len(t.spans) == len(rt.tracer.spans)
        assert len(t.instants) == len(rt.tracer.instants)
        assert sorted(t.tracks()) == sorted(rt.tracer.tracks())
    wf = round_waterfall(a)
    assert len(wf) - 2 == sum(s.name == SPAN_ROUND for s in a.spans)
    util = "\n".join(ps_utilization(a))
    assert "busy" in util and "outage" in util
    assert "retries" in retry_report(a)[0]
