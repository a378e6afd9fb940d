"""``program_trace`` and its four readers, on a small scoped trace recorded
on a TPU v5e chip (``data/v5e_scoped.xplane.pb``, made by
``record_scoped_trace.py``: a ``jit__trace`` probe with ops in the
``local_train`` and ``aggregate`` scopes, dispatched three times, each
commit sleeping 5 ms in ``asyncfleo.timing`` and 20 ms in
``asyncfleo.eval_read``), on the older probe without program spans, and
on hand-made HLO."""
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, program_trace as P  # noqa: E402
from chipbench import trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "v5e_scoped.xplane.pb")
PROBE = os.path.join(DATA, "v5e_probe.xplane.pb")
READERS = ("train_device_ms", "agg_hbm_share", "host_gap_ms_per_commit",
           "wait_gap_ms_per_commit")
PEAK = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def scoped():
    return P.load(SCOPED)


def test_every_op_is_charged_to_its_scope(scoped):
    assert len(scoped.executions) == 3
    assert scoped.ops
    assert all(o.scopes for o in scoped.ops)
    assert {o.scopes for o in scoped.ops} == {
        frozenset(["local_train"]), frozenset(["aggregate"]),
        # the multi-output fusion that ends training and starts the sum
        frozenset(["local_train", "aggregate"])}
    lt = scoped.scope_seconds("local_train")
    assert 0 < scoped.scope_seconds("aggregate") < lt


def test_the_clock_offset_puts_executions_after_their_dispatch(scoped):
    assert 0 <= scoped.offset_ns < 5e6
    dispatch = [s for n, s, _e, _a in scoped.spans if n == "dispatch"]
    shifted = [s + scoped.offset_ns for s, _e in scoped.executions]
    assert all(d <= x for d, x in zip(dispatch, shifted))
    assert len(scoped.window_executions()) == 3


def test_idle_is_charged_to_the_program_spans(scoped):
    # a gap goes whole to the span over its midpoint: the first commit's
    # 5 ms of host work is a gap of its own, the later ones join the
    # 20 ms read before them
    waits = scoped.gap_seconds(waits=True)
    host = scoped.gap_seconds(waits=False)
    assert waits > 3 * 0.02
    assert 0.005 < host < 0.02
    assert [g.span for g in scoped.gaps] == ["timing"] + 3 * ["eval_read"]
    busy = trace_reduce.busy_seconds(trace_reduce.load(SCOPED),
                                     *scoped.window)
    assert waits + host <= scoped.window_s - busy + 1e-9


def test_span_arguments_are_read(scoped):
    (args,) = {tuple(sorted(a.items())) for n, _s, _e, a in scoped.spans
               if n == "dispatch"}
    args = dict(args)
    assert (args["participants"], args["carried"], args["params"]) \
        == (1024, 0, 1024)
    assert [a["epoch"] for n, _s, _e, a in scoped.spans
            if n == "commit"] == [0, 1, 2]


def _ctx_for(tmp_path, monkeypatch, path, commits):
    dst = tmp_path / "trace" / "cell"
    dst.mkdir(parents=True)
    shutil.copy(path, dst / "t.xplane.pb")
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    tr = trace_reduce.reduce(str(dst / "t.xplane.pb"))
    return {"trace": tr, "commits": commits, "peak": PEAK}


def _read(name, ctx):
    mod = harness.load_module(
        os.path.join(ROOT, "chipbench", "metrics", name + ".py"),
        "test_metric_" + name)
    return mod.read(ctx)


def test_readers_on_the_scoped_trace(tmp_path, monkeypatch, scoped):
    ctx = _ctx_for(tmp_path, monkeypatch, SCOPED, commits=3)
    got = {name: _read(name, ctx) for name in READERS}
    assert got["train_device_ms"] == pytest.approx(
        1e3 * scoped.scope_seconds("local_train") / 3)
    module_s, count = ctx["trace"]["module_time"]("jit__trace")
    assert got["train_device_ms"] <= 1e3 * module_s / count
    # the probe's dispatch arguments do not describe its aggregate work:
    # read the arithmetic only
    needed = 3 * (1024 + 0 + 2) * 1024 * 4
    assert got["agg_hbm_share"] == pytest.approx(
        100 * needed / (scoped.scope_seconds("flatten", "aggregate")
                        * 819e9))
    assert got["wait_gap_ms_per_commit"] > 20
    assert 0 < got["host_gap_ms_per_commit"] < got["wait_gap_ms_per_commit"]
    ctx["commits"] = 0
    assert _read("wait_gap_ms_per_commit", ctx) is None


def test_readers_return_none_without_program_spans(tmp_path, monkeypatch):
    ctx = _ctx_for(tmp_path, monkeypatch, PROBE, commits=3)
    assert all(_read(name, ctx) is None for name in READERS)
    assert all(_read(name, {"trace": None, "commits": 3}) is None
               for name in READERS)


def test_a_trace_of_another_window_is_refused(tmp_path, monkeypatch):
    ctx = _ctx_for(tmp_path, monkeypatch, SCOPED, commits=3)
    ctx["trace"] = dict(ctx["trace"], window_s=ctx["trace"]["window_s"] + 1)
    with pytest.raises(ValueError, match="window"):
        P.for_run(ctx)


def _module(*instructions_by_comp, root_ids):
    hlo = P._hlo_proto_class()()
    for cid, instrs in enumerate(instructions_by_comp):
        comp = hlo.hlo_module.computations.add(id=cid, root_id=root_ids[cid])
        for iid, name, opcode, op_name, operands, called in instrs:
            ins = comp.instructions.add(id=iid, name=name, opcode=opcode)
            ins.metadata.op_name = op_name
            ins.operand_ids.extend(operands)
            ins.called_computation_ids.extend(called)
    return hlo.SerializeToString()


def test_a_fusion_goes_to_its_roots_scope():
    fused = [(10, "p", "parameter", "", [], []),
             (11, "dot", "dot", "jit(_trace)/local_train/dot", [10], []),
             (12, "add", "add", "jit(_trace)/aggregate/add", [11], []),
             (13, "convert", "convert", "", [12], [])]
    body = [(20, "q", "parameter", "", [], []),
            (21, "conv", "convolution", "jit(_trace)/local_train/conv",
             [20], []),
            (22, "fusion.9", "fusion", "", [21], [3]),
            (23, "copy.5", "copy", "", [22], [])]
    xla_own = [(30, "p.3", "parameter", "", [], []),
               (31, "neg", "negate", "", [30], [])]
    entry = [(1, "x", "parameter", "", [], []),
             (2, "fusion.1", "fusion", "jit(_trace)/local_train/dot", [1],
              [1]),
             (3, "copy.1", "copy", "", [2], []),
             (4, "while.4", "while",
              "jit(_trace)/local_train/vmap(while)", [1], []),
             (5, "while.7", "while", "", [1], [2])]
    scopes = P.hlo_scopes(_module(entry, fused, body, xla_own,
                                  root_ids=[3, 13, 23, 31]))
    lt, ag = frozenset(["local_train"]), frozenset(["aggregate"])
    # the root is XLA's convert: its operand names the scope
    assert scopes["fusion.1"] == ag
    assert scopes["while.4"] == lt
    assert scopes["copy.1"] == frozenset()
    # a loop XLA rebuilt without a name takes its body's one scope, and
    # XLA's own ops in that body (a copy, a fusion of its own) take it too
    assert scopes["while.7"] == lt
    assert scopes["copy.5"] == scopes["fusion.9"] == lt


def test_a_multi_output_fusion_is_charged_to_each_outputs_scope():
    two = [(10, "p", "parameter", "", [], []),
           (11, "norm", "reduce", "jit(_trace)/group_dist/reduce", [10], []),
           (12, "dot", "dot", "jit(_trace)/aggregate/dot_general", [10], []),
           (13, "cvt", "convert", "", [12], []),
           (14, "tuple", "tuple", "", [11, 13], [])]
    one = [(20, "q", "parameter", "", [], []),
           (21, "a", "add", "jit(_trace)/flatten/add", [20], []),
           (22, "b", "bitcast", "", [21], []),
           (23, "tuple.1", "tuple", "", [21, 22], [])]
    entry = [(1, "x", "parameter", "", [], []),
             (2, "multi", "fusion", "", [1], [1]),
             (3, "single", "fusion", "", [1], [2]),
             (4, "out", "tuple", "", [2, 3], [])]
    scopes = P.hlo_scopes(_module(entry, two, one, root_ids=[4, 14, 23]))
    assert scopes["multi"] == frozenset(["group_dist", "aggregate"])
    assert scopes["single"] == frozenset(["flatten"])


def test_xla_made_ops_take_the_scope_their_users_share():
    # XLA lowers the bank's concatenate to a chain of in-place updates
    # and names only the last; a copy feeding two scopes stays unmapped
    entry = [(1, "x", "parameter", "", [], []),
             (2, "bitcast.1", "bitcast", "", [1], []),
             (3, "dus.1", "dynamic-update-slice", "", [1, 2], []),
             (4, "dus.2", "dynamic-update-slice", "", [3, 2], []),
             (5, "dus.3", "dynamic-update-slice",
              "jit(_trace)/flatten/concatenate", [4, 2], []),
             (6, "copy.1", "copy", "", [1], []),
             (7, "dot", "dot", "jit(_trace)/aggregate/dot_general",
              [5, 6], []),
             (8, "norm", "reduce", "jit(_trace)/group_dist/reduce", [6], []),
             (9, "named", "copy", "jit(_trace)/convert_element_type", [7],
              []),
             (10, "out", "tuple", "", [8, 9], [])]
    scopes = P.hlo_scopes(_module(entry, root_ids=[10]))
    fl = frozenset(["flatten"])
    assert scopes["dus.1"] == scopes["dus.2"] == scopes["bitcast.1"] == fl
    assert scopes["copy.1"] == frozenset()       # users in two scopes
    # a named op outside every scope is not moved into its users'
    assert scopes["named"] == frozenset()
    assert scopes["x"] == frozenset()            # users differ


def test_scope_of_reads_a_path_component():
    assert P.scope_of("jit(_trace)/group_dist/norm") == "group_dist"
    assert P.scope_of("jit(_trace)/flatten_stacked/concat") is None
    assert P.scope_of("") is None
