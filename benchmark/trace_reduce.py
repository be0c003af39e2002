"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX.

``load`` reads the trace once into plain lists:

* device operations — on a TPU the events of each ``/device:TPU:n``
  plane's "XLA Ops" line (one event per executed HLO instruction, named
  by the instruction's whole text, of which the name is kept); in a
  trace with no device plane (a CPU run, as the recorded trace under
  ``tests/``) the host events that carry an ``hlo_op`` stat stand in as
  one device;
* the benchmark's own spans — host events whose name starts with
  ``bench:`` (``jax.profiler.TraceAnnotation``), so they are on the same
  clock as the device events.

Times are seconds from the trace's own origin.  Every reduction below is
a function of those lists, so a later PR computes the same number in the
same way.
"""
import bisect
import collections
import glob
import os
import re

SPAN_PREFIX = "bench:"
OP_LINE = "XLA Ops"

Trace = collections.namedtuple("Trace", "devices spans extent memo")
# devices: {plane name: [(name, start_s, dur_s)]}, sorted by start
# spans:   [(name, start_s, dur_s)], sorted by start
# extent:  (first start, last end) over everything recorded
# memo:    {} — the covers built from a million events, built once


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name):
    """A TPU trace names an operation by its whole HLO instruction,
    ``%fusion.7 = bf16[...] fusion(...)``: keep the instruction's name."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def load(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    lo, hi = float("inf"), 0.0
    planes = list(data.planes)
    has_device = any(p.name.startswith("/device:TPU") for p in planes)
    for plane in planes:
        is_dev = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            for ev in line.events:
                start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                name = op_name(ev.name)
                if is_dev:
                    if line.name == OP_LINE:
                        devices.setdefault(plane.name, []).append(
                            (name, start, dur))
                        lo, hi = min(lo, start), max(hi, start + dur)
                    continue
                if plane.name.startswith("/host:") and dur > 0:
                    lo, hi = min(lo, start), max(hi, start + dur)
                if name.startswith(SPAN_PREFIX):
                    spans.append((name[len(SPAN_PREFIX):], start, dur))
                elif not has_device and dur > 0 and any(
                        k == "hlo_op" for k, _ in ev.stats):
                    devices.setdefault("host-as-device", []).append(
                        (name, start, dur))
    for evs in devices.values():
        evs.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    if lo > hi:
        lo = hi = 0.0
    return Trace(devices, spans, (lo, hi), {})


# ----------------------------------------------------------------------
# intervals
# ----------------------------------------------------------------------
def merged(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Cover:
    """A sorted, disjoint set of intervals that answers "how many
    seconds of [lo, hi) do you cover" by bisection: a trace holds a
    million operations, and a reader asks once per span."""

    def __init__(self, disjoint):
        self.starts = [s for s, _ in disjoint]
        self.ends = [e for _, e in disjoint]
        self.before = [0.0]                 # seconds covered before each
        for s, e in disjoint:
            self.before.append(self.before[-1] + (e - s))

    def until(self, t):
        """Seconds covered in (-inf, t)."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i] - max(0.0, self.ends[i - 1] - t)

    def within(self, lo, hi):
        return self.until(hi) - self.until(lo) if hi > lo else 0.0

    def gaps(self, lo, hi):
        """The (start, end) stretches of [lo, hi) that are not covered."""
        cursor = lo
        for s, e in zip(self.starts, self.ends):
            if e <= lo:
                continue
            if s >= hi:
                break
            if s > cursor:
                yield cursor, s
            cursor = max(cursor, e)
        if cursor < hi:
            yield cursor, hi


def busy_cover(trace, plane=None):
    """When ``plane`` (default: the first device) was busy."""
    plane = sorted(trace.devices)[0] if plane is None else plane
    if plane not in trace.memo:
        trace.memo[plane] = Cover(merged(
            (s, s + d) for _, s, d in trace.devices[plane]))
    return trace.memo[plane]


def busy_seconds(trace, lo=None, hi=None):
    """Seconds in which an operation ran on the device, averaged over
    the devices in the trace."""
    lo = trace.extent[0] if lo is None else lo
    hi = trace.extent[1] if hi is None else hi
    if not trace.devices:
        return 0.0
    return sum(busy_cover(trace, p).within(lo, hi)
               for p in trace.devices) / len(trace.devices)


def window_seconds(trace):
    return trace.extent[1] - trace.extent[0]


def idle_pct(trace):
    """Share of the traced window in which no operation ran on the
    device, in percent; None for a trace with no device operations."""
    window = window_seconds(trace)
    if not trace.devices or window <= 0:
        return None
    return 100.0 * (1.0 - busy_seconds(trace) / window)


# ----------------------------------------------------------------------
# the breakdown
# ----------------------------------------------------------------------
def stem(name):
    """``fusion.535`` -> ``fusion``: a step of thousands of operations
    is read by their families."""
    return re.sub(r"[.\d]+$", "", name) or name


def top_ops(trace, k=10, key=lambda name: name):
    """``[[name, seconds]]`` of the device operations that took most
    time, summed over calls (and over ``key(name)``, e.g. ``stem``) and
    averaged over devices."""
    total = collections.Counter()
    for evs in trace.devices.values():
        for name, _, d in evs:
            total[key(name)] += d
    n = max(1, len(trace.devices))
    return [[name, sec / n] for name, sec in total.most_common(k)]


class SpanIndex:
    """The benchmark's spans, for "which span was the host in at t"."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda e: e[1])
        self.starts = [s for _, s, _ in self.spans]
        self.longest = max((d for _, _, d in self.spans), default=0.0)

    def at(self, t):
        """The innermost span that covers ``t``: the one that started
        last among those still open."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.starts[i] >= t - self.longest:
            name, s, d = self.spans[i]
            if t < s + d:
                return name
            i -= 1
        return "outside the benchmark's spans"


def idle_gaps(trace, k=10):
    """``[[what the host was doing, seconds]]``: the first device's idle
    time inside the traced window, each gap named by the benchmark span
    that covers its middle, summed by name."""
    if not trace.devices:
        return []
    index = SpanIndex(trace.spans)
    total = collections.Counter()
    for s, e in busy_cover(trace).gaps(*trace.extent):
        total[index.at(0.5 * (s + e))] += e - s
    return [[name, sec] for name, sec in total.most_common(k)]


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
def spans_named(trace, name):
    return [(s, d) for n, s, d in trace.spans if n == name]


def device_seconds_within(trace, name):
    """Device-busy seconds that fall inside the spans called ``name``
    (first device), and how many such spans there are."""
    if not trace.devices:
        return 0.0, 0
    busy = busy_cover(trace)
    spans = spans_named(trace, name)
    return sum(busy.within(s, s + d) for s, d in spans), len(spans)


def self_seconds(trace, name, children):
    """Per span called ``name``: its duration minus what the spans named
    in ``children`` cover of it."""
    kids = Cover(merged((s, s + d) for n, s, d in trace.spans
                        if n in children))
    return [d - kids.within(s, s + d) for s, d in spans_named(trace, name)]


def op_seconds(trace, names):
    """Total device seconds and number of events of the operations whose
    name is in ``names`` (first device)."""
    if not trace.devices:
        return 0.0, 0
    evs = [d for n, _, d in trace.devices[sorted(trace.devices)[0]]
           if n in names]
    return sum(evs), len(evs)


# ----------------------------------------------------------------------
# kernels, through the compiled program's text
# ----------------------------------------------------------------------
_ROW = re.compile(r"^(\d+)\s+(.*)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def custom_calls_by_file(hlo_text):
    """``{source file's base name: [instruction names]}`` of the TPU
    custom calls (Pallas kernels) in a compiled program's text, each
    placed by the innermost frame of its ``stack_frame_id``."""
    tables = {t: {} for t in _TABLES}
    table, calls = None, []
    for line in hlo_text.splitlines():
        s = line.strip()
        if s in _TABLES:
            table = s
            continue
        if table is not None:
            m = _ROW.match(s)
            if m:
                tables[table][m.group(1)] = m.group(2)
                continue
            if s:
                table = None
        if 'custom_call_target="tpu_custom_call"' in line:
            name = _INSTR.match(line)
            frame = re.search(r"stack_frame_id=(\d+)", line)
            src = re.search(r'source_file="([^"]+)"', line)
            calls.append((name.group(1) if name else "?",
                          frame.group(1) if frame else None,
                          src.group(1) if src else None))
    out = {}
    for name, frame, src in calls:
        if src is None and frame is not None:
            loc = re.search(r"file_location_id=(\d+)",
                            tables["StackFrames"].get(frame, ""))
            fid = re.search(r"file_name_id=(\d+)",
                            tables["FileLocations"].get(
                                loc.group(1), "") if loc else "")
            src = tables["FileNames"].get(fid.group(1), "").strip('"') \
                if fid else None
        out.setdefault(os.path.basename(src) if src else "?", []).append(name)
    return out
