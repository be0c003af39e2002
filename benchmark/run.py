"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with as many chips as the cell asks for: anything else is an
error (non-zero exit, no result line).  Builds, warms every program the
cell's traffic can reach (set-up), measures for ``--seconds``, compares
what the timed path produced with the plain reference, and prints one
JSON object as the last line of standard output.  ``--trace 0`` reports
the cell's end-to-end metrics; ``--trace 1`` its per-layer metrics, read
from the profiler's trace of the window by one reader file per metric
(``benchmark/metrics/<name>.py``).

The cell's configuration (``benchmark/configs/``), its traffic
(``benchmark/workloads/``) and its metrics are found by the names in
BENCHMARK.json; the driver by the traffic file's ``kind``
(``benchmark/drivers/<kind>.py``).  A later PR adds files and entries and
edits nothing here.

For setting limits (not used by the driver's runs):
``--readings 1,2,3 [--control bfloat16|fp8] [--fault half_batch]`` reads
the numbers ``correct`` compares on several seeds in one process and
holds each to the workload file's limits, as a run does.
"""
import time
T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def find_chips(chips):
    """The devices of this run, or exit: the benchmark runs on a TPU
    that holds the chips the cell asks for, and nowhere else."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < chips:
        sys.exit(f"bench: the cell asks for {chips} chip(s); JAX found "
                 f"{len(devices)} {dev.device_kind}")
    return devices


def metrics_of(bench, group, cell_name, reported=()):
    """The metrics of ``group`` that this cell reports: those that list
    it; of those with no list, every end-to-end metric, and each
    per-layer metric whose ``moves`` the cell reports."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            keep = cell_name in m["workloads"]
        else:
            keep = group == "end_to_end" or m["moves"] in reported
        if keep:
            out.append(m)
    return out


def read_metric(name, reading):
    """Run ``benchmark/metrics/<name>.py``'s ``read``; a reader that
    finds nothing to read returns None, and the metric is left out."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(reading)


class Reading:
    """What a per-layer reader may read."""

    def __init__(self, ctx, outcome, trace, peaks):
        self.cell, self.cfg, self.mix = ctx.cell, ctx.cfg, ctx.mix
        self.facts, self.trace, self.peaks = outcome.facts, trace, peaks
        self.end_to_end = outcome.end_to_end
        self.note = ctx.note


def run_cell(ctx, bench, device, tamper=None):
    """Everything of a run after the look for a chip; returns the result
    line as a dict.  ``tamper`` is for the tests under ``tests/``."""
    from benchmark import harness, trace_reduce
    cell = ctx.cell
    driver = importlib.import_module(
        "benchmark.drivers." + ctx.mix["kind"])
    outcome = driver.run(ctx, tamper)
    t_done = time.perf_counter()
    rows, ok = harness.judge(outcome.checks, outcome.limits)
    ok = ok and outcome.failed == 0 and outcome.attempted > 0

    e2e = metrics_of(bench, "end_to_end", cell["name"])
    values = dict(outcome.end_to_end, setup_s=ctx.setup_s)
    result = {"correct": ok, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": {}}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": cell["chips"],
           "memory_peak_bytes": ctx.memory_peak_bytes}
    if not ctx.trace:
        for m in e2e:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        trace = trace_reduce.load(trace_reduce.find_xplane(ctx.trace_dir))
        reading = Reading(ctx, outcome, trace,
                          harness.peaks_for(device.device_kind))
        reported = {m["name"] for m in e2e}
        for m in metrics_of(bench, "per_layer", cell["name"], reported):
            value = read_metric(m["name"], reading)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        dev["busy_s"] = trace_reduce.busy_seconds(trace)
        dev["window_s"] = trace_reduce.window_seconds(trace)
        result["breakdown"] = {
            # five single operations, then five families of operations
            "device_ops": trace_reduce.top_ops(trace, 5)
            + [[name + ".*", sec] for name, sec in trace_reduce.top_ops(
                trace, 5, key=trace_reduce.stem)],
            "idle_gaps": trace_reduce.idle_gaps(trace, 10)}
    result["device"] = dev
    ctx.note("times", setup_s=ctx.setup_s,
             window_s=ctx.window[1] - ctx.window[0],
             close_to_checked_s=t_done - ctx.window[1],
             reading_the_trace_s=time.perf_counter() - t_done,
             whole_run_s=time.perf_counter() - ctx.t_start)
    compared = {name: {"value": value, "limit": limit, "ok": good,
                       "at": where}
                for name, value, limit, good, where in rows}
    compared["requests_failed"] = {"value": outcome.failed, "limit": 0,
                                   "ok": outcome.failed == 0}
    # the numbers compared come last, in the line and on standard error
    result["compared"] = compared
    for name, c in compared.items():
        print(f"bench: compared: {name} = {c['value']!r} limit "
              f"{c['limit']!r} {'ok' if c['ok'] else 'NOT OK'} "
              f"{c.get('at', '')}", file=sys.stderr, flush=True)
    return result


def read_seeds(ctx, args):
    """``--readings``: the driver's numbers on each seed, each set held
    to the workload file's limits as a run's are, so that a control or
    a fault shows as ``correct: false`` on the numbers the cell
    compares."""
    from benchmark import harness
    driver = importlib.import_module("benchmark.drivers." + ctx.mix["kind"])
    seeds = [int(s) for s in args.readings.split(",")]
    got = driver.readings(ctx, seeds, control=args.control, fault=args.fault)
    out = {}
    for seed, sets in got.items():
        out[str(seed)] = {}
        for who, checks in sets.items():
            rows, ok = harness.judge(checks, ctx.mix["limits"])
            out[str(seed)][who] = {
                "correct": ok,
                "compared": {name: {"value": value, "limit": limit,
                                    "ok": good, "at": where}
                             for name, value, limit, good, where in rows}}
            print(f"bench: readings: seed {seed} {who}: correct={ok} "
                  + " ".join(f"{n}={v!r}/{lim!r}" for n, v, lim, _, _ in rows),
                  file=sys.stderr, flush=True)
    return {"readings": out}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--readings", default=None,
                    help="comma-separated seeds: print the numbers "
                         "compared on each, no result line")
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from benchmark import harness
    cell, cfg, mix, bench = harness.load_cell(args.workload)
    devices = find_chips(cell["chips"])
    import jax
    # sub-second compiles are most of what a warm set-up still pays:
    # keep them in the persistent cache too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import mxtpu  # noqa: F401 — places JAX's compile cache
    ctx = harness.Context(
        cell, cfg, mix, args.seed, args.seconds, args.trace, T_START,
        trace_dir=os.path.join(HERE, ".trace", cell["name"]))
    ctx.note("start", kind=repr(devices[0].device_kind),
             count=len(devices), seed=args.seed, seconds=ctx.seconds,
             trace=args.trace,
             compile_cache_dir=jax.config.jax_compilation_cache_dir)
    if args.readings:
        print(json.dumps(read_seeds(ctx, args)), flush=True)
        return
    result = run_cell(ctx, bench, devices[0])
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
