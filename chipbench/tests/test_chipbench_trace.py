"""``trace_reduce`` on a small trace recorded on a TPU v5e chip
(``data/v5e_probe.xplane.pb``, made by ``record_trace.py``: three dispatches
of one jitted program, each followed by 20 ms in ``chipbench.eval_read``),
and on hand-made events."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace_reduce as T  # noqa: E402

PROBE = os.path.join(os.path.dirname(__file__), "data",
                     "v5e_probe.xplane.pb")


@pytest.fixture(scope="module")
def probe():
    return T.load(PROBE)


def test_probe_planes(probe):
    assert list(probe.ops) == ["/device:TPU:0"]
    names = [n for n, _s, _e in probe.modules["/device:TPU:0"]]
    assert len(names) == 3
    assert all(n.startswith("jit_probe_step") for n in names)
    spans = [n for n, _s, _e in probe.spans]
    assert spans.count("chipbench.dispatch") == 3
    assert spans.count("chipbench.eval_read") == 3
    assert spans.count("chipbench.window") == 1


def test_probe_busy_union_and_gaps(probe):
    lo, hi = T.window(probe)
    assert hi - lo == 65_112_297                   # ns, as recorded
    busy = T.union(probe.ops["/device:TPU:0"], lo, hi)
    assert all(a < b for a, b in busy)
    assert all(b1 < a2 for (_a1, b1), (a2, _b2) in zip(busy, busy[1:]))
    busy_ns = sum(b - a for a, b in busy)
    assert T.busy_seconds(probe, lo, hi) == pytest.approx(busy_ns * 1e-9)
    assert busy_ns == 107_845
    idle = T.gaps(busy, lo, hi)
    assert sum(b - a for a, b in idle) + busy_ns == hi - lo
    long = [g for g in idle if g[1] - g[0] >= T.LONG_GAP_NS]
    assert len(long) == 3                          # the three 20 ms sleeps
    assert all(g[1] - g[0] > 20_000_000 for g in long)


def test_probe_module_time(probe):
    lo, hi = T.window(probe)
    # the device clock runs about 1.3 ms behind the host's here, so the
    # first execution lies before the window's host span opens
    secs, count = T.module_time(probe, "jit_probe_step", lo, hi)
    assert count == 2
    assert secs == pytest.approx((69_468_119 - 69_414_077
                                  + 91_095_162 - 91_041_332) * 1e-9)
    assert T.module_time(probe, "jit_other", lo, hi) == (0.0, 0)


def test_probe_breakdown(probe):
    lo, hi = T.window(probe)
    bd = T.breakdown(probe, lo, hi)
    idle = dict(bd["idle_gaps"])
    assert max(idle, key=idle.get) == "chipbench.eval_read"
    assert idle["chipbench.eval_read"] > 0.06
    ops = dict(bd["device_ops"])
    assert all(" = " not in n for n in ops)
    assert "%convolution_tanh_fusion" in ops
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_reduce_reads_the_window(probe):
    r = T.reduce(PROBE)
    assert r["window_s"] == pytest.approx(0.065112297)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["module_time"]("jit_probe_step")[1] == 2


def test_union_and_gaps_by_hand():
    ev = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 38, 45),
          ("e", 100, 120)]
    assert T.union(ev, 0, 110) == [(0, 20), (30, 45), (100, 110)]
    assert T.gaps(T.union(ev, 0, 110), 0, 110) == [(20, 30), (45, 100)]
    assert T.union(ev, 50, 60) == []
    assert T.gaps([], 50, 60) == [(50, 60)]


def test_host_activity_picks_the_innermost_span():
    spans = [("chipbench.window", 0, 1000), ("chipbench.lap", 0, 900),
             ("chipbench.eval_read", 100, 200)]
    host = T.HostActivity(spans)
    assert host.at(150) == "chipbench.eval_read"
    assert host.at(500) == "chipbench.lap"
    assert host.at(950) == "host:none"


def test_short_name():
    assert T.short_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "%fusion.3"
    assert T.short_name("%while.4") == "%while.4"
