"""The program's own spans, read from a traced run's xplane.

``mxtpu.obs.region`` writes every layer boundary of the train and
generate paths as a ``jax.profiler.TraceAnnotation`` named
``mxtpu:<name>``, with its counts as the event's stats, so the spans lie
in the profiler's own trace on the device events' clock.
``trace_reduce.load`` keeps only the benchmark's ``bench:`` spans; this
file opens the same xplane again, walks the host planes for ``mxtpu:``
events and keeps them in ``r.trace.memo``, so that the readers read the
file once.  A program that writes no such span (the parent of the
PR that added them) gives an empty list, and every reader ``None``.

Counts known only when the work is done ride on a short closing child
(``<name>/done``): its stats are folded into the region that holds it
and the child is dropped.

Device-idle time is *intersected* with a span (``idle_within``): the
seconds of the span in which no operation ran on the device, whatever
the gap began or ended in — not the whole gap booked to the one span
that covers its middle, as ``trace_reduce.idle_gaps`` does.
"""
import collections
import os
import re
import statistics

from . import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PREFIX = "mxtpu:"
DONE = "/done"
MEMO = "program_spans"

Span = collections.namedtuple("Span", "name start dur stats leaf")
# name:  the region's, without the prefix
# start, dur: seconds on the trace's clock (as trace_reduce's)
# stats: {count: value}, the closing child's folded in
# leaf:  no other region of its thread lies inside it


def read_xplane(path):
    """Every region in the xplane at ``path``, sorted by start."""
    from jax.profiler import ProfileData
    raw = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    raw.setdefault((plane.name, line.name), []).append(
                        (ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                         ev.name[len(PREFIX):], dict(ev.stats)))
    return fold(raw)


def fold(by_line):
    """``{thread: [(start, dur, name, stats)]}`` -> ``[Span]``: nesting on
    a thread is the parent relation, so one pass with a stack of the
    regions still open finds each closing child's region and whether a
    region holds another."""
    out = []
    for events in by_line.values():
        open_ = []                       # [start, end, name, stats, leaf]
        # a region that starts with its child is sorted before it
        for start, dur, name, stats in sorted(
                events, key=lambda e: (e[0], -e[1])):
            while open_ and start >= open_[-1][1]:
                out.append(_close(open_.pop()))
            if name.endswith(DONE):
                # a closing child whose region opened before the trace
                # did has nothing to be folded into
                if open_ and open_[-1][2] == name[:-len(DONE)]:
                    open_[-1][3].update(stats)
                continue
            if open_:
                open_[-1][4] = False
            open_.append([start, start + dur, name, dict(stats), True])
        while open_:
            out.append(_close(open_.pop()))
    out.sort(key=lambda s: s.start)
    return out


def _close(rec):
    start, end, name, stats, leaf = rec
    return Span(name, start, end - start, stats, leaf)


def spans_of(r):
    """The run's regions (``[]`` where the program wrote none, or the
    run left no xplane), read once per run."""
    memo = r.trace.memo
    if MEMO not in memo:
        try:
            path = trace_reduce.find_xplane(
                os.path.join(HERE, ".trace", r.cell["name"]))
        except FileNotFoundError:
            memo[MEMO] = []
        else:
            memo[MEMO] = read_xplane(path)
    return memo[MEMO]


def named(r, name):
    return [s for s in spans_of(r) if s.name == name]


def mean_ms(spans):
    """Mean length in ms; None for no span."""
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / len(spans)


def unblocked_median_ms(spans):
    """Median length in ms of the spans no longer than halfway between
    the shortest and the longest of them (the median, because the one
    call that meets the full queue part way lies below that line too);
    None for no span."""
    if not spans:
        return None
    durs = [s.dur for s in spans]
    return 1e3 * statistics.median(
        d for d in durs if d <= 0.5 * (min(durs) + max(durs)))


def count_sum(spans, key):
    return sum(s.stats.get(key, 0) for s in spans)


def idle_within(r, spans):
    """Seconds inside ``spans`` in which no operation ran on the (first)
    device: each span's length minus the device-busy time it overlaps."""
    if not r.trace.devices:
        return None
    busy = trace_reduce.busy_cover(r.trace)
    return sum(s.dur - busy.within(s.start, s.start + s.dur)
               for s in spans)


def idle_ms_per(r, name, per):
    """Device-idle ms inside the regions called ``name``, per region
    called ``per``; None where either is missing."""
    inside, steps = named(r, name), named(r, per)
    idle = idle_within(r, inside) if inside and steps else None
    return None if idle is None else 1e3 * idle / len(steps)


def unattributed_idle_pct(r):
    """Share (percent) of the traced window's device-idle time that lies
    in no leaf region: what the host was doing there has no name yet."""
    spans = spans_of(r)
    if not spans or not r.trace.devices:
        return None
    lo, hi = r.trace.extent
    busy = trace_reduce.busy_cover(r.trace)
    idle = (hi - lo) - busy.within(lo, hi)
    if idle <= 0:
        return None
    leaves = trace_reduce.merged(
        (max(lo, s.start), min(hi, s.start + s.dur))
        for s in spans if s.leaf and s.start < hi and s.start + s.dur > lo)
    named_idle = sum((e - s) - busy.within(s, e) for s, e in leaves)
    return 100.0 * (1.0 - named_idle / idle)


def compiles_in_window(r):
    """How many ``compile`` regions the traced window holds; None where
    the program writes no region at all (0 cannot be told from "not
    instrumented" there)."""
    if not spans_of(r):
        return None
    return len(named(r, "compile"))


# ----------------------------------------------------------------------
# device operations by the phase of the step they belong to
# ----------------------------------------------------------------------
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$')
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=')
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r'\bcalls=%?([\w.\-]+)')


def ops_by_scope(hlo_text, scope):
    """``(inside, mixed)``: names of the compiled program's instructions
    that lie wholly, and partly, under the named scope ``scope``
    (``jax.named_scope`` puts it into the ``op_name`` metadata:
    ``jit(step)/jit(main)/train/optimizer/mul``).  A fusion is judged by
    the instructions of the computation it calls, not by the one
    operation that names it: XLA fuses across the scope's edge (a
    gradient written straight into the optimizer's bucket), and such a
    fusion is ``mixed``.  An instruction with no ``op_name`` (and a
    fusion none of whose instructions has one) is in neither."""
    needle = "/" + scope + "/"
    votes = {}          # computation -> [under the scope?] per op_name
    own, calls = {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        if op:
            own[name] = needle in "/" + op.group(1) + "/"
            votes.setdefault(comp, []).append(own[name])
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    inside, mixed = set(), set()
    for name in set(own) | set(calls):
        v = votes.get(calls.get(name)) or \
            ([own[name]] if name in own else [])
        if v and all(v):
            inside.add(name)
        elif any(v):
            mixed.add(name)
    return inside, mixed


def device_share_pct(r, scope, part="inside"):
    """Device seconds of the operations wholly under ``scope``
    (``part="inside"``) or of the fusions that straddle its edge
    (``"mixed"``) / device-busy seconds, in percent; None where the
    program names no such scope."""
    text = r.facts.get("hlo_text")
    if not text or not r.trace.devices:
        return None
    key = ("ops_by_scope", scope)
    if key not in r.trace.memo:
        r.trace.memo[key] = ops_by_scope(text, scope)
    inside, mixed = r.trace.memo[key]
    busy = trace_reduce.busy_seconds(r.trace)
    if not inside or busy <= 0:
        return None
    in_s, in_events = trace_reduce.op_seconds(r.trace, inside)
    mixed_s, _ = trace_reduce.op_seconds(r.trace, mixed)
    if part == "inside":
        # which families of operations the scope holds, and how much of
        # each family straddles it or lies outside: names do not tell
        by = {k: collections.Counter() for k in ("in", "mixed", "out")}
        for name, _, dur in r.trace.devices[sorted(r.trace.devices)[0]]:
            by["in" if name in inside else
               "mixed" if name in mixed else "out"][
                trace_reduce.stem(name)] += dur
        top = (by["in"] + by["mixed"]).most_common(6)
        r.note("device_share", scope=scope, instructions=len(inside),
               mixed_instructions=len(mixed), events=in_events,
               device_s=in_s, mixed_s=mixed_s, busy_s=busy,
               families_in_mixed_out_s=[
                   (k, round(by["in"][k], 4), round(by["mixed"][k], 4),
                    round(by["out"][k], 4)) for k, _ in top])
    if not in_events:
        return None
    return 100.0 * (in_s if part == "inside" else mixed_s) / busy
