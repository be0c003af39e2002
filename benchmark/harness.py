"""What every driver shares: the run's context, spans, the traced
window, the device's memory and the result line.

The spans are the benchmark's own, put round the calls into each layer
from outside the program (``jax.profiler.TraceAnnotation``, so that they
land on the device trace's clock).  With ``--trace 0`` a span is a no-op:
end-to-end numbers are taken with tracing off.
"""
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import time

from .trace_reduce import SPAN_PREFIX

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MAX_TRACE_S = 10.0


@dataclasses.dataclass
class Outcome:
    """What a driver hands back."""
    attempted: int
    failed: int
    end_to_end: dict            # name -> value (setup_s is added by run)
    checks: dict                # name -> (value, where)
    limits: dict                # name -> limit
    facts: dict                 # what the per-layer readers may read


class Context:
    def __init__(self, cell, cfg, mix, seed, seconds, trace, t_start,
                 trace_dir=None):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.seed, self.trace = seed, bool(trace)
        # a traced run's window is the traced stretch, and that is short:
        # traces are large and the tracer slows the host
        self.seconds = min(seconds, float(mix.get("trace_seconds",
                                                  MAX_TRACE_S))) \
            if trace else seconds
        self.t_start = t_start
        self.trace_dir = trace_dir
        self.setup_s = None
        self.window = None
        self.memory_peak_bytes = None
        self._tracing = False

    # -- talk ------------------------------------------------------------
    def note(self, phase, **facts):
        print(f"bench: {self.cell['name']}: {phase}: " + " ".join(
            f"{k}={v}" for k, v in facts.items()), file=sys.stderr,
            flush=True)

    # -- spans -----------------------------------------------------------
    def span(self, name):
        if not self._tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def wrap(self, obj, attr, name):
        """Put a span round ``obj.attr`` on the instance (traced runs
        only; a later call finds the wrapper by attribute lookup)."""
        if not self.trace:
            return
        inner = getattr(obj, attr)

        def wrapped(*a, **kw):
            with self.span(name):
                return inner(*a, **kw)

        setattr(obj, attr, wrapped)

    # -- the window --------------------------------------------------------
    def open_window(self):
        """Set-up ends here.  A traced run starts the profiler first
        (that is not set-up the user pays, and not window either)."""
        self.setup_s = time.perf_counter() - self.t_start
        if self.trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            # one trace at a time is kept, for a look by hand
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True

    def stop_trace(self):
        if self._tracing:
            import jax
            self._tracing = False
            jax.profiler.stop_trace()

    def close_window(self, t0, t1):
        self.stop_trace()
        self.window = (t0, t1)

    def read_memory(self):
        import jax
        peaks = []
        for d in jax.devices()[:self.cell["chips"]]:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = max(peaks)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name):
    """(cell, configuration, mix, the whole BENCHMARK.json) by the
    names in ``BENCHMARK.json``."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(HERE, "workloads",
                                 cell["traffic"] + ".json"))
    return cell, cfg, mix, bench


def peaks_for(device_kind):
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} "
                         f"in peaks.json — add it with its source")
    return table[device_kind]


def judge(checks, limits):
    """``[(name, value, limit, ok)]`` and whether all hold.  A number
    with no limit in the workload file is an error, not a pass."""
    rows, ok = [], True
    for name, (value, where) in checks.items():
        if name not in limits:
            raise SystemExit(f"bench: no limit for {name!r} in the "
                             f"workload file")
        limit = limits[name]
        if limit is None:           # named in PERF.md as not compared
            rows.append((name, value, None, True, where))
            continue
        good = bool(value == value and value <= limit)   # NaN fails
        ok = ok and good
        rows.append((name, value, limit, good, where))
    return rows, ok
