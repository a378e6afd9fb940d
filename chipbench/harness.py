"""The chip benchmark's harness: cells found by name, one run driven lap by lap.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything that belongs to one of them
is a data file found by its name:

    chipbench/configs/<config>.json   the deployment and local model
    chipbench/mixes/<traffic>.json    the strategy and the accuracy target
    chipbench/models/<model_kind>.py  the local model: its plain reference
                                      and its build with the program
    chipbench/rules/<agg_mode>.py     the reference's commit rule
    chipbench/metrics/<metric>.py     one reader per per-layer metric
    chipbench/flops/<model_kind>.py   forward FLOPs per training sample

All of them are found under one root, the checkout the cell came from
(``load_cell``'s ``root``, kept as ``Cell.root``; the repository's by
default), so a test can run a cell whose files exist only elsewhere.

Set-up builds the run from those files alone, with the program's public
constructors: the Walker constellation, the link and the timing of the
simulation, the strategy named by the mix with the mix's ``spec`` fields
put over it, and what the model kind's ``build(cfg, seed, local_iters)``
returns: the pool that trains the local model on the seeded data, the
evaluator and the initial model ``w0`` (host arrays).  A kind's ``build``
imports the program inside its body only; its reference part
(``reference.py`` lists it) imports nothing of the program.

A *lap* is one whole simulation of the cell's scenario from ``w0`` to the
configuration's horizon: ``FLSimulation.run`` -> ``EventDrivenRuntime.run``
-> the fused ``EpochStepProgram``.  Each lap builds a fresh
``FLSimulation`` over the same pool and evaluator, so every lap does the
same work and the fused programs, cached on the pool, compile once.

Set-up builds the run and drives one warm lap, which compiles exactly the
cell's own shapes.  The window then runs further laps and ends at the first
lap end after ``--seconds``, on a device-complete result: every lap reads
its commits' accuracies.  Whole laps keep the work of a window fixed: a
window cut inside a lap would weigh the lap's parts by where the cut fell
(a FedAsync lap spends its device time in a few training commits).

The harness wraps instances, never the program's source: host spans
(``jax.profiler.TraceAnnotation``, names ``chipbench.*``) go around the
contact-plan queries, the input gather, the fused dispatch and the
evaluator's reads.  The first commits of every lap are captured for the
comparison with the plain reference (``reference.py``, ``check.py``): the
first commit's step arguments and outputs (its bank or block sums, and
distances; held on the device, read only after the window), and each
commit's time, models, weights, gamma, stale groups and new global model.

A cell whose ``chips`` is more than one runs on a mesh over that many
devices, one axis named ``"data"`` (the axis the program shards over),
handed to ``make_epoch_program`` and ``SimConfig``.  A one-chip cell
passes ``mesh=None``.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time
from typing import Dict, List

import numpy as np

from chipbench import check, reference
from chipbench.reference import ROOT, load_module  # noqa: F401 (tests)

OUT_DIR = os.path.join(ROOT, ".chipbench_out")

# the program folds its seed into uint32 arithmetic (sim seed * 1000 +
# epoch), so the run seed must stay under 2**32 / 1000
_SEED_RANGE = 4_000_000

SPAN_PREFIX = "chipbench."
SPAN_WINDOW = SPAN_PREFIX + "window"
SPAN_LAP = SPAN_PREFIX + "lap"
SPAN_PLAN = SPAN_PREFIX + "contact_plan"
SPAN_GATHER = SPAN_PREFIX + "input_gather"
SPAN_DISPATCH = SPAN_PREFIX + "dispatch"
SPAN_EVAL_READ = SPAN_PREFIX + "eval_read"
SPAN_EVAL_ASYNC = SPAN_PREFIX + "eval_dispatch"
SPAN_LAP_BUILD = SPAN_PREFIX + "lap_build"

# the strategy fields a mix states, because the reference follows them
SPEC_KEYS = ("ps_scenario", "agg_mode", "use_isl", "num_groups")


class NoChip(RuntimeError):
    """JAX found no accelerator of a known kind, or too few of them."""


def run_seed(seed: int, config: Dict, root: str = ROOT) -> int:
    """The seed handed to the program and the reference, drawn from the
    driver's ``--seed`` (any whole number, also past 32 bits).

    The seed draws the data, and the data set how many samples every
    satellite trains on (its shard, cut to the smallest; the model kind's
    ``shard_rows``); that count is a shape of the compiled programs.  So
    the first candidate whose shards hold the configuration's
    ``shard_rows`` is taken: every seed runs the same shapes and compiles
    nothing new, with other data."""
    kind = reference.load_kind(config["model_kind"], root)
    for k in itertools.count():
        entropy = [int(seed) % 2 ** 64, k]
        state = np.random.SeedSequence(entropy).generate_state(1, np.uint32)
        cand = int(state[0]) % _SEED_RANGE
        if kind.shard_rows(cand, config) == config["shard_rows"]:
            return cand


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: str = ROOT             # where its files were found

    @property
    def blocking(self) -> bool:
        """A target makes the program read every commit's accuracy as it
        commits; without one it reads them all at the lap's end."""
        return self.mix["target"] is not None

    def metrics(self, kind: str) -> List[Dict]:
        """The cell's end_to_end or per_layer metrics (those whose
        ``workloads`` list, where given, names the cell)."""
        out = []
        for m in getattr(self, kind):
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            out.append(m)
        return out


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = _load_json(os.path.join(root, "chipbench", "mixes",
                                  w["traffic"] + ".json"))
    missing = set(SPEC_KEYS) - set(mix["spec"])
    if missing:
        raise ValueError(f"mix {w['traffic']}: spec lacks {sorted(missing)}")
    return Cell(name, int(w["chips"]), config, mix, bench["end_to_end"],
                bench["per_layer"], root)


def load_peaks(root: str = ROOT) -> Dict:
    return _load_json(os.path.join(root, "chipbench", "peaks.json"))


def check_device(chips: int, peaks: Dict):
    """The accelerator JAX sees; raises NoChip unless it is a TPU of a kind
    in the peak table and there are at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX's default device is {dev.platform}, not a TPU")
    if dev.device_kind not in peaks:
        raise NoChip(f"{dev.device_kind!r} is not in chipbench/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return dev


def forward_flops(config: Dict, root: str = ROOT) -> int:
    return int(reference.found("flops", config["model_kind"], root)
               .forward_flops(config))


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts, while open, the backend compiles (or persistent-cache loads)
    and the traces that JAX reports through ``jax.monitoring``."""

    def __init__(self):
        from jax._src import dispatch
        self.compiles = 0
        self.traces = 0
        self._compile_event = dispatch.BACKEND_COMPILE_EVENT
        self._trace_event = dispatch.JAXPR_TRACE_EVENT

    def _on_event(self, event: str, _duration: float, **_kw) -> None:
        if event == self._compile_event:
            self.compiles += 1
        elif event == self._trace_event:
            self.traces += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


@dataclasses.dataclass
class Capture:
    """What a lap's first commits produced, for the reference check."""
    ids: object = None           # padded participant ids (device)
    wv_bank: object = None       # their aggregation weights (device)
    stack: object = None         # bank (C, N) or block sums (B, N) (device)
    dists: object = None         # new-orbit distances (device)
    dw_row: object = None
    dw_seg: object = None
    kpad: int = 0
    blocked_m: int = 0
    participants: tuple = ()
    commits: list = dataclasses.field(default_factory=list)
    form: str = "bank"           # the configuration's step_output
    per_orbit: int = 1
    by_orbit: bool = True        # the commit rule's BLOCKS is "orbit"
    size_of: object = None       # the program's data size of a satellite


class Bench:
    """One run of one cell: set-up, warm lap, measured window."""

    def __init__(self, cell: Cell, seed: int):
        self.cell = cell
        self.seed = run_seed(seed, cell.config, cell.root)
        self.kind = reference.load_kind(cell.config["model_kind"], cell.root)
        self.local_iters = int(cell.config["local_iters"])
        form = cell.config.get("step_output", "bank")
        if form not in check.FORMS:
            raise ValueError(f"step_output {form!r}: one of {check.FORMS}")
        rule = reference.load_rule(cell.mix["spec"]["agg_mode"], cell.root)
        self.capture_layout = dict(
            form=form, by_orbit=rule.BLOCKS == "orbit",
            per_orbit=int(cell.config["constellation"]["sats_per_orbit"]))
        self.capture = Capture()
        self._capture_next = False
        self._weights = None
        self._lap_commits: List[tuple] = []   # (t_agg, wall) this lap
        self.in_window = False
        self._reset_counts()

    def _reset_counts(self) -> None:
        self.commit_walls: List[float] = []   # window commit read times
        self.window_accuracies: List = []     # window commits' accuracy
        self.sim_seconds = 0.0                # simulated s covered
        self.commits = 0
        self.useful_flops = 0.0
        self.dispatch_s = 0.0                 # host s inside dispatches
        self.lap_walls: List[float] = []      # wall s of each lap
        self.segments: Dict[str, float] = {}
        self.lap_count = 0

    # ---- set-up ------------------------------------------------------------

    def build(self):
        """The run as the configuration and mix files state it."""
        self.t_build = time.perf_counter()
        from repro.core import FLSimulation, SimConfig
        from repro.core.constellation import WalkerDelta
        from repro.core.epoch_step import make_epoch_program
        from repro.core.links import LinkModel
        from repro.fl import get_strategy

        cfg, mix, seed = self.cell.config, self.cell.mix, self.seed
        self.mesh = make_mesh(self.cell.chips)
        self.spec = dataclasses.replace(get_strategy(mix["strategy"]),
                                        **mix["spec"])
        self.simcfg = SimConfig(
            event_driven=True, seed=seed,
            duration_s=float(cfg["horizon_s"]), dt_s=float(cfg["dt_s"]),
            train_time_s=float(cfg["train_time_s"]),
            agg_timeout_s=float(cfg["agg_timeout_s"]),
            min_models=int(cfg["min_models"]), mesh=self.mesh,
            link=LinkModel(rate_bps=float(cfg["link_rate_bps"]),
                           proc_delay_s=float(cfg["proc_delay_s"])))
        with _span(SPAN_PREFIX + "build"):
            t0 = time.perf_counter()
            const = WalkerDelta(**cfg["constellation"])
            pool, evaluator, self.w0 = self.kind.build(cfg, seed,
                                                       self.local_iters)
            fls = FLSimulation(self.spec, pool, evaluator, self.simcfg,
                               const)
            self.build_s = time.perf_counter() - t0
        self.pool = pool
        self.evaluator = _Evaluator(fls.evaluator)
        self.constellation = const
        self.fwd_flops = forward_flops(cfg, self.cell.root)
        self.pool.epoch_inputs = _spanned(self.pool.epoch_inputs, SPAN_GATHER)
        self.prog = make_epoch_program(self.pool, self.w0, mesh=self.mesh,
                                       use_kernel=self.spec.use_agg_kernel)
        self.prog._step = _Dispatch(self.prog._step, self)
        return self

    # ---- laps --------------------------------------------------------------

    def _new_sim(self):
        from repro.core import FLSimulation
        with _span(SPAN_LAP_BUILD):
            fls = FLSimulation(self.spec, self.pool, self.evaluator,
                               self.simcfg, self.constellation)
        fls.plan.downlink_times = _spanned(fls.plan.downlink_times, SPAN_PLAN)
        fls.plan.uplink_times = _spanned(fls.plan.uplink_times, SPAN_PLAN)
        fls._fused_commit = _Commit(fls._fused_commit, self)
        fls._mode_weights = _Weights(fls._mode_weights, self)
        fls._record_epoch = _Record(fls._record_epoch, self)
        return fls

    def lap(self, max_commits: int = 10 ** 9) -> None:
        """One whole lap, from ``w0`` to the horizon (or its first
        ``max_commits`` commits)."""
        fls = self._new_sim()
        self.fls = fls
        self._lap_commits = []
        self.lap_count += 1
        t0 = time.perf_counter()
        with _span(SPAN_LAP):
            fls.run(self.w0, max_epochs=max_commits,
                    target_accuracy=self.cell.mix["target"])
        self.lap_walls.append(time.perf_counter() - t0)
        self._end_lap(fls)

    def _end_lap(self, fls) -> None:
        if self._lap_commits:
            self.sim_seconds += max(t for t, _w in self._lap_commits)
        if not self.cell.blocking and self.in_window:
            # a lazy lap is device-complete once run() has read every
            # accuracy; its commits complete together at the lap's end
            now = time.perf_counter()
            self.commit_walls.extend([now] * len(self._lap_commits))
        for k, v in fls.segment_seconds.items():
            self.segments[k] = self.segments.get(k, 0.0) + v

    def warm(self) -> None:
        self.t_warm = time.perf_counter()
        self.lap()
        self._reset_counts()

    def window(self, seconds: float) -> None:
        """The measured window: laps until the stopping rule holds."""
        self.in_window = True
        self.t_start = time.perf_counter()
        deadline = self.t_start + float(seconds)
        self.prog_counts0 = (self.prog.dispatches,
                             self.prog.fallback_dispatches)
        with _span(SPAN_WINDOW):
            self.lap()
            while time.perf_counter() < deadline:
                self.lap()
        self.t_end = time.perf_counter()
        self.in_window = False

    # ---- results -----------------------------------------------------------

    @property
    def wall_s(self) -> float:
        return self.t_end - self.t_start

    def commit_intervals_ms(self) -> List[float]:
        walls = [self.t_start] + self.commit_walls
        return [1e3 * (b - a) for a, b in zip(walls, walls[1:])]

    def window_dispatches(self) -> int:
        d0, f0 = self.prog_counts0
        return ((self.prog.dispatches - d0)
                + (self.prog.fallback_dispatches - f0))


def make_mesh(chips: int):
    """None for one chip; else a mesh over the first ``chips`` devices,
    one axis ``"data"`` (the program's own constructor, the axis
    ``Auto``)."""
    if chips <= 1:
        return None
    import jax
    from repro.launch.mesh import make_mesh as program_mesh
    return program_mesh((chips,), ("data",), devices=jax.devices()[:chips])


def _spanned(fn, name: str):
    def wrapped(*args, **kwargs):
        with _span(name):
            return fn(*args, **kwargs)
    return wrapped


class _Dispatch:
    """Stands in for the fused program's jitted step: a host span around
    each dispatch, and the outputs of a lap's first commit."""

    def __init__(self, fn, bench: Bench):
        self.fn = fn
        self.bench = bench

    def __call__(self, *args):
        b = self.bench
        t0 = time.perf_counter()
        with _span(SPAN_DISPATCH):
            out = self.fn(*args)
        if b.in_window:
            b.dispatch_s += time.perf_counter() - t0
        if b._capture_next:
            b._capture_next = False
            (_w, _carry, _inputs, ids, _seed, wv_bank, _wv_carry, _base_w,
             dw_row, dw_seg, kpad, blocked_m, _dw_carry, _ref) = args
            _new_w, stack, dists, _losses = out
            c = b.capture
            c.ids, c.wv_bank, c.stack, c.dists = ids, wv_bank, stack, dists
            c.dw_row, c.dw_seg = dw_row, dw_seg
            c.kpad, c.blocked_m = int(kpad), int(blocked_m)
        return out


class _Commit:
    """Wraps ``FLSimulation._fused_commit``: counts the training work and
    starts a lap's capture at its first commit."""

    def __init__(self, fn, bench: Bench):
        self.fn = fn
        self.bench = bench

    def __call__(self, prog, beta, ids_np, participants, t_agg, used, late,
                 train_epoch=None):
        b = self.bench
        if beta == 0:
            b._capture_next = bool(participants)
            b.capture = Capture(participants=tuple(int(s) for s in
                                                   participants),
                                size_of=b.pool.data_size,
                                **b.capture_layout)
        if participants and b.in_window:
            cfg = b.cell.config
            b.useful_flops += (3.0 * b.fwd_flops * b.local_iters
                               * cfg["batch_size"] * len(participants))
        return self.fn(prog, beta, ids_np, participants, t_agg, used, late,
                       train_epoch=train_epoch)


class _Weights:
    """Wraps ``FLSimulation._mode_weights``: the commit's models and the
    aggregation weights it gave them."""

    def __init__(self, fn, bench: Bench):
        self.fn = fn
        self.bench = bench

    def __call__(self, metas, beta, groups):
        out = self.fn(metas, beta, groups)
        self.bench._weights = (metas, out[0], out[1])
        return out


class _Record:
    """Wraps ``FLSimulation._record_epoch``: the commit's completion time,
    and what the first commits of a lap produced."""

    def __init__(self, fn, bench: Bench):
        self.fn = fn
        self.bench = bench

    def __call__(self, history, beta, t_agg, metas, info, lazy_eval, w_tree):
        acc = self.fn(history, beta, t_agg, metas, info, lazy_eval, w_tree)
        b = self.bench
        now = time.perf_counter()
        b._lap_commits.append((float(t_agg), now))
        w_metas, ws, base_w = b._weights
        rec = history[-1]
        keep_w = len(b.capture.commits) < reference.MAX_COMMITS
        b.capture.commits.append({
            "t_agg": float(t_agg),
            "models": [(int(m.sat_id), int(m.epoch)) for m in metas],
            "weights": {int(m.sat_id): float(v)
                        for m, v in zip(w_metas, ws)},
            "base_weight": float(base_w), "gamma": rec.gamma,
            "stale_groups": rec.stale_groups,
            "num_models": rec.num_models, "w": w_tree if keep_w else None})
        b._weights = None
        if b.in_window:
            b.commits += 1
            b.window_accuracies.append(acc)
            if b.cell.blocking:
                b.commit_walls.append(now)
        return acc


class _Evaluator:
    """The pool's evaluator with host spans around its reads."""

    def __init__(self, inner):
        self.inner = inner
        self.labels = inner.labels

    def eval_async(self, params):
        with _span(SPAN_EVAL_ASYNC):
            return self.inner.eval_async(params)

    def __call__(self, params) -> float:
        with _span(SPAN_EVAL_READ):
            return self.inner(params)
