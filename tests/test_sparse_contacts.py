"""Sparse contact compilation parity + multi-PS plans (DESIGN.md §14).

The sparse timeline replaces the dense (T, S, P) visibility grid with
segment-based contact windows and must be *bit-identical* to the dense
path everywhere it is observable: the compiled window set, every plan
query, and — the strongest pin — full event-driven runtime histories at
S ∈ {40, 200}.  Multi-PS plans (``hapring:N``, P > 3) are exercised
end-to-end through the same runtime.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import FLSimulation, SimConfig
from repro.core.constellation import (WalkerDelta, make_ps_nodes,
                                      paper_constellation)
from repro.fl import get_strategy
from repro.sched import ContactPlan, EventDrivenRuntime
from repro.sched.faults import FaultModel

from test_epoch_step import TinyFusedTrainer, W0
from test_sched import SIMKW, _rows

GEOMETRIES = {
    "paper-twohap": (paper_constellation(), "twohap"),
    "paper-hap": (paper_constellation(), "hap"),
    "walker200-ring4": (WalkerDelta(num_orbits=10, sats_per_orbit=20,
                                    altitude_m=600e3,
                                    inclination_deg=60.0), "hapring:4"),
    # two of the four HAPs never see the one satellite: empty window lists
    "single-sat-ring4": (WalkerDelta(num_orbits=1, sats_per_orbit=1,
                                     altitude_m=500e3,
                                     inclination_deg=40.0), "hapring:4"),
}


def _plans(key, duration_s=6 * 3600.0, dt_s=30.0):
    cst, scenario = GEOMETRIES[key]
    nodes = make_ps_nodes(scenario)
    dense = ContactPlan.compile(cst, nodes, duration_s, dt_s)
    sparse = ContactPlan.compile(cst, nodes, duration_s, dt_s,
                                 visibility="sparse")
    return dense, sparse


def _sim2(name, visibility, *, constellation=None, spec_kw=None, **kw):
    cfg = SimConfig(event_driven=True, visibility=visibility,
                    **{**SIMKW, **kw})
    spec = get_strategy(name)
    if spec_kw:
        spec = dataclasses.replace(spec, **spec_kw)
    return FLSimulation(spec, TinyFusedTrainer(W0), None, cfg,
                        constellation=constellation)


# ---- window-for-window parity ---------------------------------------------

@pytest.mark.parametrize("key", sorted(GEOMETRIES))
def test_sparse_windows_match_dense(key):
    dense, sparse = _plans(key)
    wd, ws = dense.windows(), sparse.windows()
    assert len(wd) == len(ws) > 0
    for a, b in zip(wd, ws):
        assert (a.sat, a.node) == (b.sat, b.node)
        assert a.t_start == b.t_start and a.t_end == b.t_end
        assert a.delay_s == b.delay_s


@pytest.mark.parametrize("key", sorted(GEOMETRIES))
def test_sparse_plan_queries_match_dense(key):
    dense, sparse = _plans(key)
    assert dense.summary() == sparse.summary()
    sats = np.arange(0, dense.num_sats, 3)
    rng = np.random.default_rng(5)
    for t in rng.uniform(0.0, 6 * 3600.0, size=40):
        td, pd = dense.next_contact(sats, float(t))
        ts, ps = sparse.next_contact(sats, float(t))
        np.testing.assert_array_equal(td, ts)
        np.testing.assert_array_equal(pd, ps)
        np.testing.assert_array_equal(dense.next_contact_by_node(float(t)),
                                      sparse.next_contact_by_node(float(t)))


def test_sparse_timeline_point_queries_match_dense():
    dense, sparse = _plans("paper-twohap")
    tld, tls = dense.timeline, sparse.timeline
    rng = np.random.default_rng(9)
    for t in rng.uniform(0.0, 6 * 3600.0, size=25):
        np.testing.assert_array_equal(tld.visible(float(t)),
                                      tls.visible(float(t)))
        for p in range(len(dense.nodes)):
            np.testing.assert_array_equal(tld.visible_sats(float(t), p),
                                          tls.visible_sats(float(t), p))
    for sat in range(0, dense.num_sats, 7):
        np.testing.assert_allclose(tld.visibility_fraction(sat),
                                   tls.visibility_fraction(sat))
    assert tld.covered_steps() == tls.covered_steps()
    for p in range(len(dense.nodes)):
        for a, b in zip(tld.node_windows(p), tls.node_windows(p)):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(tld.node_cover(p), tls.node_cover(p)):
            np.testing.assert_array_equal(a, b)


# ---- runtime-history bit-parity at S in {40, 200} --------------------------

@pytest.mark.parametrize("name,cst", [
    ("asyncfleo-twohap", None),                       # S=40 paper geometry
    ("asyncfleo-hap", None),
    ("asyncfleo-twohap", WalkerDelta(num_orbits=10, sats_per_orbit=20,
                                     altitude_m=600e3,
                                     inclination_deg=60.0)),  # S=200
])
def test_sparse_runtime_history_bit_identical(name, cst):
    """Dense and sparse visibility produce byte-identical event-driven
    histories AND exactly equal aggregated weights — the acceptance pin
    that sparse compilation changes nothing observable."""
    a = _sim2(name, "dense", constellation=cst)
    b = _sim2(name, "sparse", constellation=cst)
    ra, rb = EventDrivenRuntime(a), EventDrivenRuntime(b)
    ha = ra.run(W0, max_epochs=3)
    hb = rb.run(W0, max_epochs=3)
    assert _rows(ha) == _rows(hb)
    np.testing.assert_array_equal(np.asarray(a._w_flat),
                                  np.asarray(b._w_flat))
    assert ra.events.counts == rb.events.counts


# ---- multi-PS (P > 3) plans end-to-end -------------------------------------

@pytest.mark.parametrize("n_ps", [4, 6])
def test_hapring_multi_ps_end_to_end(n_ps):
    """A P>3 hapring compiles per-PS channel pools and completes an
    event-driven run: every ring PS appears in the contact plan and the
    sink handoff walks the full ring."""
    cst = WalkerDelta(num_orbits=10, sats_per_orbit=20,
                      altitude_m=600e3, inclination_deg=60.0)
    fls = _sim2("asyncfleo-gs", "sparse", constellation=cst,
                spec_kw={"ps_scenario": f"hapring:{n_ps}"})
    assert len(fls.nodes) == n_ps
    assert all(n.kind == "hap" for n in fls.nodes)
    nodes_seen = {w.node for w in fls.plan.windows()}
    assert nodes_seen == set(range(n_ps))
    rt = EventDrivenRuntime(fls)
    hist = rt.run(W0, max_epochs=3)
    assert len(hist) == 3
    assert all(r.num_models > 0 for r in hist)
    # round sinks rotate across the ring rather than pinning one PS,
    # and every sink is a valid ring member
    sinks = {rnd.sink for rnd in rt.rounds.values()}
    assert len(sinks) >= 2
    assert sinks <= set(range(n_ps))


def test_hapring_rejects_empty_ring():
    with pytest.raises(ValueError):
        make_ps_nodes("hapring:0")


# ---- sparse-mode guard rails ----------------------------------------------

def test_sparse_rejects_grid_mask_faults():
    """Eclipse/outage fault models mutate the dense grid in place; the
    sparse timeline has no grid, so construction must fail loudly."""
    with pytest.raises(ValueError, match="sparse"):
        _sim2("asyncfleo-twohap", "sparse",
              fault_model=FaultModel(eclipse_fraction=0.25))
    with pytest.raises(ValueError, match="sparse"):
        _sim2("asyncfleo-twohap", "sparse",
              fault_model=FaultModel(ps_outage_fraction=0.1))


def test_unknown_visibility_mode_rejected():
    with pytest.raises(ValueError, match="visibility"):
        _sim2("asyncfleo-twohap", "banana")
