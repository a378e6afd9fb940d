"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics with the profiler off;
``--trace 1`` records a profiler trace of the window and reports the
per-layer metrics.  Both check the first commits of the window's last lap
against the plain reference and print each compared number beside its
limit, as the last lines on standard error and under ``checks``, the last
key of the result.

The last line on standard output is one JSON object: ``correct``,
``attempted`` (commits in the window), ``failed`` (those whose accuracy was
not finite), ``metrics``, ``device`` and, traced, ``breakdown``.  Without a
TPU of a kind in ``chipbench/peaks.json`` the run prints no result and
exits non-zero.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def p90(values):
    """The 90th percentile by Python's exclusive quantiles."""
    return statistics.quantiles(values, n=10)[-1]


END_TO_END = {
    "setup_s": lambda bench, setup_s: setup_s,
    "sim_day_s": lambda bench, _s: bench.wall_s / (bench.sim_seconds
                                                   / 86400.0),
    "commit_ms.p90": lambda bench, _s: p90(bench.commit_intervals_ms()),
}


def reader_context(bench, peak, trace):
    return {"build_s": bench.build_s, "wall_s": bench.wall_s,
            "commits": bench.commits, "segments": bench.segments,
            "dispatch_s": bench.dispatch_s,
            "dispatches": bench.window_dispatches(),
            "useful_flops": bench.useful_flops, "peak": peak,
            "trace": trace}


def measure(bench, seconds: float, trace: bool, dev, peak):
    """The window of a warmed run and its metrics.  Returns the result
    without ``correct`` and the lines for standard error."""
    from chipbench import harness, trace_reduce

    trace_dir = os.path.join(harness.OUT_DIR, "trace", bench.cell.name)
    if trace:
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    with harness.CompileCounter() as compiles:
        bench.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    setup_s = bench.t_start - T_PROCESS
    stats = dev.memory_stats() or {}
    failed = sum(1 for a in bench.window_accuracies
                 if not math.isfinite(float(a)))
    lines = [f"window: {bench.commits} commits in {bench.lap_count} laps, "
             f"{bench.wall_s!r} s, {bench.sim_seconds!r} simulated s; "
             f"compiles in the window {compiles.compiles}, traces "
             f"{compiles.traces}",
             f"setup_s {setup_s!r}: start to build "
             f"{bench.t_build - T_PROCESS!r} s, build {bench.build_s!r} s, "
             f"warm lap {bench.t_start - bench.t_warm!r} s",
             f"window laps (wall s): {bench.lap_walls!r}"]
    result = {"attempted": bench.commits, "failed": failed, "metrics": {}}
    cell = bench.cell
    if trace:
        tr = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        ctx = reader_context(bench, peak, tr)
        for m in cell.metrics("per_layer"):
            mod = harness.load_module(
                os.path.join(harness.BENCH_DIR, "metrics", m["name"] + ".py"),
                "chipbench_metric_" + m["name"].replace(".", "_"))
            v = mod.read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        extra = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
        result["breakdown"] = tr["breakdown"]
    else:
        for m in cell.metrics("end_to_end"):
            value = END_TO_END[m["name"]](bench, setup_s)
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
        extra = {}
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(_devices()),
                        "memory_peak_bytes": stats.get("peak_bytes_in_use"),
                        **extra}
    return result, lines


def verify(cell, prog, seed: int, local_iters: int, ref=None):
    """The captured first commits (``check.host_capture``) against the
    reference of ``seed`` (a run seed).  Returns (correct, the numbers
    with their limits, lines for standard error)."""
    from chipbench import check, reference
    cfg = cell.config
    t0 = time.perf_counter()
    if ref is None:
        ref = reference.lap(cfg, cell.mix["spec"], seed,
                            local_iters=local_iters)
    nums = check.compare(prog, ref, cfg["constellation"]["sats_per_orbit"])
    ok, lines = check.judge(nums, cfg["limits"])
    lines.insert(0, f"reference check {time.perf_counter() - t0!r} s, "
                    f"{len(ref.commits)} commits followed")
    checks = {k: {"value": v, "limit": cfg["limits"].get(k)}
              for k, v in nums.items()}
    return ok, checks, lines


def run_cell(cell, seed: int, seconds: float, trace: bool, dev, peak):
    """Set-up, window, metrics, then the reference check once the
    program's state is freed.  Returns the result and the stderr lines."""
    from chipbench import check, harness

    bench = harness.Bench(cell, seed).build()
    bench.warm()
    result, lines = measure(bench, seconds, trace, dev, peak)
    prog = check.host_capture(bench.capture)
    run_seed, iters = bench.seed, bench.local_iters
    del bench
    gc.collect()
    ok, checks, more = verify(cell, prog, run_seed, iters)
    result["correct"] = bool(ok and result["failed"] == 0)
    result["checks"] = checks          # the last key of the result line
    return result, lines + more


def _devices():
    import jax
    return jax.devices()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    # every program, however quick to compile, goes to the cache, so a
    # second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from chipbench import harness
    cell = harness.load_cell(args.workload)
    peaks = harness.load_peaks()
    try:
        dev = harness.check_device(cell.chips, peaks)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result, lines = run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), dev, peaks[dev.device_kind])
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
