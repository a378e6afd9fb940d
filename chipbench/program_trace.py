"""The program's own spans and named scopes in a traced window.

The simulator writes host spans named ``asyncfleo.*`` into the profiler's
trace (``repro/obs/span.py``), with their arguments, and its fused epoch
program names its parts with ``jax.named_scope``: ``local_train``,
``flatten``, ``aggregate``, ``group_dist``.  This module reads one run's
trace (the newest ``*.xplane.pb`` under ``.chipbench_out/trace/``) and
returns

* the ``asyncfleo.*`` host spans with their arguments;
* the device ops of the fused program's executions (modules named
  ``jit__trace``), each charged to its named scope;
* the idle gaps of at least ``LONG_GAP_NS`` in the window, each charged to
  the innermost ``asyncfleo.*`` span that covers its midpoint.

Op to scope: the profiler keeps each executed module's HLO in the
``/host:metadata`` plane.  An op is the HLO instruction its event names;
a fusion is charged to the ``op_name`` of its fused computation's root
instruction (a multi-output fusion to that of each output), any other op
to its own ``op_name``, and the scope is the first component of that name
that is one of ``SCOPES``.  An op that names no scope itself takes, in
turn, the one its called instructions name (a loop XLA rebuilt), its
loop's where it runs in a scoped loop's body, or, where XLA made it, the
one all its users take (``hlo_scopes``).  Where the module's HLO is
missing, the op event's ``tf_op`` stat (its ``op_name``) is read instead.
An op that none of these names is unmapped: it is counted and reported,
never guessed.

Clock: the device cannot start an execution of the fused program before
the ``asyncfleo.dispatch`` span that enqueued it began.  Executions and
dispatch spans are paired in order, and the device times are shifted by
the largest (dispatch start - execution start), clamped at 0: the least
offset that puts every execution after its dispatch.

The file is parsed with the ``xplane_pb2`` module of the installed
TensorFlow, loaded by its path (TensorFlow itself is not imported), and
a schema of the few HLO fields read here.  A trace without the program's
spans (a program that has none) reads as empty: the readers then return
``None``.

    python3 chipbench/program_trace.py <trace.xplane.pb>

prints the summary (clock offset, scope seconds, the server scopes' ops,
unmapped ops, idle by span, and the op that ran first after each long
wait) as JSON.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import importlib.util
import json
import os
import sys
from typing import Dict, FrozenSet, List, Optional, Tuple

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace_reduce  # noqa: E402

PREFIX = "asyncfleo."
WINDOW_SPAN = "chipbench.window"
MODULE_PREFIX = "jit__trace"
SCOPES = ("local_train", "flatten", "aggregate", "group_dist")
WAIT_SPANS = ("eval_read", "dist_read")       # the host waits on the device
LONG_GAP_NS = trace_reduce.LONG_GAP_NS
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"

Span = Tuple[str, float, float, Dict]         # (name, start_ns, end_ns, args)


@dataclasses.dataclass
class Op:
    name: str              # the HLO instruction
    start: float           # ns, on the device clock
    end: float
    scopes: FrozenSet[str]  # of SCOPES; empty where unmapped


@dataclasses.dataclass
class Gap:
    start: float           # ns, host clock (device times shifted)
    end: float
    span: Optional[str]    # innermost asyncfleo span (without prefix)
    next_op: Optional[str]


@dataclasses.dataclass
class ProgramTrace:
    window: Tuple[float, float]
    spans: List[Span]                  # asyncfleo.* (names without prefix)
    executions: List[Tuple[float, float]]   # jit__trace, device clock
    ops: List[Op]                      # ops inside those executions
    offset_ns: Optional[float]         # device -> host clock shift
    gaps: List[Gap]                    # long idle gaps in the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def in_window(self, name: str) -> List[Span]:
        lo, hi = self.window
        return [s for s in self.spans if s[0] == name and lo <= s[1] < hi]

    def window_executions(self) -> List[Tuple[float, float]]:
        """Executions whose shifted start lies in the window."""
        lo, hi = self.window
        off = self.offset_ns or 0.0
        return [(s, e) for s, e in self.executions if lo <= s + off < hi]

    def window_ops(self) -> List[List[Op]]:
        """The ops of each window execution (``ops`` is in start order)."""
        starts = [o.start for o in self.ops]
        return [self.ops[bisect.bisect_left(starts, s):
                         bisect.bisect_left(starts, e)]
                for s, e in self.window_executions()]

    def scope_seconds(self, *scopes: Optional[str]) -> float:
        """Device seconds (union) of the window executions' ops charged
        to any of ``scopes`` (``None``: the unmapped ops)."""
        want = set(scopes)
        total = 0.0
        for ops in self.window_ops():
            evs = [(o.name, o.start, o.end) for o in ops
                   if (o.scopes & want if o.scopes else None in want)]
            total += sum(b - a for a, b in trace_reduce.union(
                evs, -np.inf, np.inf))
        return total * 1e-9

    def unmapped(self) -> Dict[str, float]:
        """Unmapped ops of the window executions: device seconds by op."""
        out: Dict[str, float] = {}
        for ops in self.window_ops():
            for o in ops:
                if not o.scopes:
                    out[o.name] = out.get(o.name, 0.0) + (o.end - o.start)
        return {k: v * 1e-9 for k, v in out.items()}

    def gap_seconds(self, waits: bool) -> float:
        """Idle seconds under a program span: the host's waits on the
        device (``waits``), or its own work."""
        return 1e-9 * sum(g.end - g.start for g in self.gaps
                          if g.span is not None
                          and (g.span in WAIT_SPANS) == waits)


# ---- reading the file -------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("program_trace needs the installed tensorflow's "
                          "xplane_pb2")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _hlo_proto_class():
    """``xla.HloProto`` cut to the fields read here (their numbers are
    hlo.proto's; a parser skips every other field)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="chipbench/hlo_subset.proto", package="chipbench_hlo",
        syntax="proto3")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            fd = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                fd.type_name = ".chipbench_hlo." + tname

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msg("OpMetadata", ("op_name", 2, F.TYPE_STRING, one, None))
    msg("Instruction", ("name", 1, F.TYPE_STRING, one, None),
        ("opcode", 2, F.TYPE_STRING, one, None),
        ("metadata", 7, F.TYPE_MESSAGE, one, "OpMetadata"),
        ("id", 35, F.TYPE_INT64, one, None),
        ("operand_ids", 36, F.TYPE_INT64, many, None),
        ("called_computation_ids", 38, F.TYPE_INT64, many, None))
    msg("Computation", ("name", 1, F.TYPE_STRING, one, None),
        ("instructions", 2, F.TYPE_MESSAGE, many, "Instruction"),
        ("id", 5, F.TYPE_INT64, one, None),
        ("root_id", 6, F.TYPE_INT64, one, None))
    msg("Module", ("name", 1, F.TYPE_STRING, one, None),
        ("computations", 3, F.TYPE_MESSAGE, many, "Computation"))
    msg("Hlo", ("hlo_module", 1, F.TYPE_MESSAGE, one, "Module"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_hlo.Hlo"))


def scope_of(op_name: str) -> Optional[str]:
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return None


def hlo_scopes(hlo_bytes: bytes) -> Dict[str, FrozenSet[str]]:
    """Instruction name -> the scopes it is charged to, for every
    instruction of one module; an empty set where it is unmapped.

    In order, an instruction takes:

    * the scope of its ``op_name``; a fusion, that of its fused
      computation's root.  XLA's own instructions at a root (a convert, a
      bitcast, a copy) carry none: the root is then followed to its first
      operand until an instruction names one, and a fusion whose root path
      names none keeps its own.  A multi-output fusion (a tuple root) does
      the work of every output: it is charged to each scope they name;
    * where its name gives no scope and it calls computations (a while
      loop XLA rebuilt, a fusion of XLA's own), the scopes of the
      instructions it calls, where they all name the same ones;
    * where it runs inside the body of a scoped loop (a copy XLA put
      there), the loop's scopes;
    * where XLA made it (no ``op_name``) and none of the above applies,
      the scopes that all its users take: the chain of in-place updates
      XLA makes of a concatenate, the bitcasts and copies that feed one
      scope's op.  An instruction whose users differ, or that has none,
      stays unmapped."""
    hlo = _hlo_proto_class()()
    hlo.ParseFromString(hlo_bytes)
    comps = {c.id: c for c in hlo.hlo_module.computations}
    by_id = {i.id: i for c in comps.values() for i in c.instructions}
    none: FrozenSet[str] = frozenset()
    memo: Dict[int, FrozenSet[str]] = {}

    def named(at) -> Optional[FrozenSet[str]]:
        """The scopes the ``op_name``s at the end of a root path give;
        ``None`` where no instruction on the path has a name."""
        while at is not None and not at.metadata.op_name \
                and at.opcode not in ("fusion", "tuple") and at.operand_ids:
            at = by_id.get(at.operand_ids[0])
        if at is None:
            return None
        if at.opcode == "tuple":
            outs = [named(by_id.get(o)) for o in at.operand_ids]
            outs = [o for o in outs if o is not None]
            return frozenset().union(*outs) if outs else None
        if at.opcode == "fusion" and at.called_computation_ids:
            found = root_scopes(at.called_computation_ids[0])
            if found is not None:
                return found
        if not at.metadata.op_name:
            return None
        found = scope_of(at.metadata.op_name)
        return frozenset([found]) if found else none

    def root_scopes(comp_id: int) -> Optional[FrozenSet[str]]:
        comp = comps.get(comp_id)
        return named(by_id.get(comp.root_id)) if comp is not None else None

    def scope(ins) -> FrozenSet[str]:
        if ins.id not in memo:
            memo[ins.id] = none            # a guard against cycles
            found = None
            if ins.opcode == "fusion" and ins.called_computation_ids:
                found = root_scopes(ins.called_computation_ids[0])
            if found is None:
                own = scope_of(ins.metadata.op_name)
                found = frozenset([own]) if own else none
            if not found and ins.called_computation_ids:
                inner = {scope(i) for cid in ins.called_computation_ids
                         if cid in comps for i in comps[cid].instructions}
                inner.discard(none)
                found = inner.pop() if len(inner) == 1 else none
            memo[ins.id] = found
        return memo[ins.id]

    out = {ins.name: scope(ins) for ins in by_id.values()}

    def inherit(comp_id: int, outer: FrozenSet[str], seen: set) -> None:
        # an op that runs inside a scoped loop's body is part of its work
        if comp_id in seen or comp_id not in comps:
            return
        seen.add(comp_id)
        for ins in comps[comp_id].instructions:
            if not out[ins.name]:
                out[ins.name] = outer
            if ins.opcode != "fusion":
                for cid in ins.called_computation_ids:
                    inherit(cid, out[ins.name], seen)

    for ins in by_id.values():
        if ins.opcode != "fusion" and out[ins.name]:
            for cid in ins.called_computation_ids:
                inherit(cid, out[ins.name], set())

    users: Dict[int, List[str]] = {}
    for ins in by_id.values():
        for o in ins.operand_ids:
            users.setdefault(o, []).append(ins.name)
    pending = [ins for ins in by_id.values() if not out[ins.name]
               and not ins.metadata.op_name and ins.id in users]
    changed = True
    while changed:                         # a chain resolves from its end
        changed = False
        for ins in pending:
            if not out[ins.name]:
                found = {out[u] for u in users[ins.id]}
                if len(found) == 1 and none not in found:
                    out[ins.name] = found.pop()
                    changed = True
    return out


def _stat_value(stat, names):
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    if kind == "ref_value":
        return names.get(stat.ref_value)
    return getattr(stat, kind)


def _program_id(module: str) -> int:
    """A module's program id, from its name: ``jit__trace(<id>)``."""
    return int(module.rsplit("(", 1)[-1].rstrip(")"))


def _instruction(event_name: str) -> str:
    """An op event's name is its HLO instruction text; keep its name."""
    return trace_reduce.short_name(event_name).lstrip("%")


def load(path: str) -> ProgramTrace:
    pb = _xplane_pb2()
    space = pb.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    hlo: Dict[int, bytes] = {}
    spans: List[Span] = []
    window = None
    device = None
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if plane.name == METADATA_PLANE:
            for em in plane.event_metadata.values():
                for st in em.stats:
                    if names.get(st.metadata_id) == HLO_STAT:
                        hlo[_program_id(em.name)] = st.bytes_value
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = plane.event_metadata[ev.metadata_id].name
                    start = line.timestamp_ns + ev.offset_ps / 1000.0
                    end = start + ev.duration_ps / 1000.0
                    if name == WINDOW_SPAN:
                        window = (start, end)
                    elif name.startswith(PREFIX):
                        args = {names.get(st.metadata_id):
                                _stat_value(st, names) for st in ev.stats}
                        spans.append((name[len(PREFIX):], start, end, args))
        elif (plane.name.startswith(trace_reduce.DEVICE_PREFIX)
              and (device is None or plane.name < device[0])):
            device = (plane.name, plane)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    spans.sort(key=lambda s: s[1])
    return _reduce(window, spans, device[1] if device else None, hlo)


def _reduce(window, spans, plane, hlo) -> ProgramTrace:
    executions: List[Tuple[float, float, int]] = []
    all_ops: List[Tuple[str, float, float, int, Optional[str]]] = []
    if plane is not None:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}                      # metadata id -> (op, program, tf_op)
        for line in plane.lines:
            if line.name not in (trace_reduce.OPS_LINE,
                                 trace_reduce.MODULES_LINE):
                continue
            for ev in line.events:
                em = plane.event_metadata[ev.metadata_id]
                start = line.timestamp_ns + ev.offset_ps / 1000.0
                end = start + ev.duration_ps / 1000.0
                if line.name == trace_reduce.MODULES_LINE:
                    if em.name.startswith(MODULE_PREFIX):
                        executions.append((start, end,
                                           _program_id(em.name)))
                    continue
                if ev.metadata_id not in meta:
                    stats = {names.get(st.metadata_id):
                             _stat_value(st, names) for st in em.stats}
                    meta[ev.metadata_id] = (
                        _instruction(em.name),
                        int(stats.get("program_id") or 0),
                        stats.get("tf_op"))
                name, pid, tf_op = meta[ev.metadata_id]
                all_ops.append((name, start, end, pid, tf_op))
    all_ops.sort(key=lambda o: o[1])
    executions.sort()
    scopes = {pid: hlo_scopes(b) for pid, b in hlo.items()
              if any(pid == x[2] for x in executions)}
    starts = [x[0] for x in executions]
    ops: List[Op] = []
    for name, s, e, pid, tf_op in all_ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= executions[i][1] or pid != executions[i][2]:
            continue
        if pid in scopes and name in scopes[pid]:
            charged = scopes[pid][name]
        else:
            own = scope_of(tf_op or "")
            charged = frozenset([own]) if own else frozenset()
        ops.append(Op(name, s, e, charged))
    dispatch = [s[1] for s in spans if s[0] == "dispatch"]
    offset = None
    if dispatch and len(dispatch) == len(executions):
        offset = max(0.0, max(d - x[0] for d, x in zip(dispatch,
                                                       executions)))
    gaps = (_gaps(window, spans, [(n, s, e) for n, s, e, _p, _t in all_ops],
                  offset) if offset is not None else [])
    return ProgramTrace(window, spans, [(s, e) for s, e, _p in executions],
                        ops, offset, gaps)


def _gaps(window, spans, device_ops, offset) -> List[Gap]:
    """Long idle gaps of the device (``device_ops`` in start order)."""
    lo, hi = window
    shifted = [(n, s + offset, e + offset) for n, s, e in device_ops]
    first = [s for _n, s, _e in shifted]
    host = _Innermost(spans)
    out = []
    for s, e in trace_reduce.gaps(trace_reduce.union(shifted, lo, hi),
                                  lo, hi):
        if e - s < LONG_GAP_NS:
            continue
        j = bisect.bisect_left(first, e)
        out.append(Gap(s, e, host.at((s + e) / 2),
                       shifted[j][0] if j < len(shifted) else None))
    return out


class _Innermost:
    """The innermost ``asyncfleo.*`` span covering an instant."""

    def __init__(self, spans: List[Span]):
        self.names = [n for n, _s, _e, _a in spans]
        self.start = np.array([s for _n, s, _e, _a in spans], np.float64)
        self.end = np.array([e for _n, _s, e, _a in spans], np.float64)

    def at(self, t: float) -> Optional[str]:
        cover = np.flatnonzero((self.start <= t) & (t < self.end))
        if not len(cover):
            return None
        return self.names[int(cover[np.argmin(self.end[cover]
                                              - self.start[cover])])]


# ---- the run's trace, for the readers ---------------------------------------

_CACHE: Dict[Tuple[str, float], ProgramTrace] = {}


def for_run(ctx) -> Optional[ProgramTrace]:
    """The traced run's program trace, or ``None`` where the run was not
    traced or its program wrote no ``asyncfleo.*`` span."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    from chipbench import harness
    path = trace_reduce.find_xplane(os.path.join(harness.OUT_DIR, "trace"))
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = load(path)
    pt = _CACHE[key]
    if abs(pt.window_s - tr["window_s"]) > 1e-6:
        raise ValueError(f"{path}: its window ({pt.window_s} s) is not the "
                         f"run's ({tr['window_s']} s)")
    return pt if pt.spans else None


def summary(pt: ProgramTrace, waits: int = 8) -> Dict:
    """What ``PERF.md`` records of one trace."""
    execs = pt.window_executions()
    dispatch = [s for n, s, _e, _a in pt.spans if n == "dispatch"]
    lag_ms = sorted((x - d) * 1e-6 for d, (x, _e)
                    in zip(dispatch, pt.executions))
    idle: Dict[str, float] = {}
    for g in pt.gaps:
        k = g.span or "none"
        idle[k] = idle.get(k, 0.0) + (g.end - g.start) * 1e-9
    long_waits = sorted((g for g in pt.gaps if g.span in WAIT_SPANS),
                        key=lambda g: g.start - g.end)[:waits]
    puts = pt.in_window("input_put")
    unmapped = pt.unmapped()
    server: Dict[Tuple[str, str], List[float]] = {}
    for ops in pt.window_ops():
        for o in ops:
            if o.scopes and "local_train" not in o.scopes:
                k = ("+".join(sorted(o.scopes)), o.name)
                server.setdefault(k, []).append((o.end - o.start) * 1e-3)
    commit_ms = sorted((e - s) * 1e-6 for _n, s, e, _a
                       in pt.in_window("commit"))
    return {
        "window_s": pt.window_s, "offset_ms": (None if pt.offset_ns is None
                                               else pt.offset_ns * 1e-6),
        "executions": len(execs),
        "dispatch_to_start_ms": ({"min": lag_ms[0],
                                  "median": lag_ms[len(lag_ms) // 2]}
                                 if lag_ms else None),
        "scope_s": {s: pt.scope_seconds(s) for s in SCOPES},
        "server_ops_us": [[k[0], k[1], len(v), sum(v) / len(v)] for k, v
                          in sorted(server.items(),
                                    key=lambda kv: -sum(kv[1]))[:16]],
        "unmapped": {"ops": len(unmapped), "s": pt.scope_seconds(None),
                     "top_s": dict(sorted(unmapped.items(),
                                          key=lambda kv: -kv[1])[:5])},
        "commits": len(commit_ms),
        "commit_ms": ({"min": commit_ms[0],
                       "median": commit_ms[len(commit_ms) // 2],
                       "max": commit_ms[-1]} if commit_ms else None),
        "idle_by_span_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "input_put": {"count": len(puts),
                      "bytes": sum(int(a.get("bytes") or 0)
                                   for _n, _s, _e, a in puts),
                      "s": sum(e - s for _n, s, e, _a in puts) * 1e-9},
        "long_waits": [{"span": g.span, "ms": (g.end - g.start) * 1e-6,
                        "next_op": g.next_op} for g in long_waits],
    }


if __name__ == "__main__":
    print(json.dumps(summary(load(sys.argv[1])), indent=1))
