"""The serve loop's device-idle time, split by the leaf regions it lies in.

Since ISSUE 36 every stretch of host work on the serving thread lies in
one leaf region of the program (``mxtpu.obs.region``): the runner's
``/stage``, ``/dispatch`` and ``/fetch``, and the
batcher's ``gen/admit``, ``gen/prefill/rows``, ``gen/decode_rows``,
``gen/sample``, ``gen/commit``, ``gen/fire``, ``gen/complete`` and
``gen/between``.  Each leaf is counted by exactly one reader, chosen by
the region it lies under: ``gen/decode`` (the decode trio, one reader a
child), ``gen/prefill`` (``prefill_host_idle_ms``), or neither
(``batcher_host_idle_ms``).  The idle time inside is intersected
exactly, as ``program_spans.idle_within`` does, with each leaf cut to
the traced window as ``program_spans.unattributed_idle_pct`` cuts it, so
that the readers and the unattributed share add up to the window's idle
time.

A leaf's owner is read off its name where the name says it (a call's
children, a chunk's rows), else off the time: the serve loop writes all
its regions on its one thread.  ``gen/step`` also carries the thread's CPU
time and its collector pauses over the step (``cpu_us``, ``gc_us``,
``gc_n``), written only while a trace is.  A program that writes none of
what a reader needs (the parent of the PR that added it, the train cell)
gives None.
"""
import bisect

from . import program_spans, trace_reduce

OWNERS = ("gen/decode", "gen/prefill")


def _holder(spans):
    """``at(t)``: whether one of ``spans`` (which do not overlap) holds
    the instant ``t``."""
    spans = sorted((s.start, s.start + s.dur) for s in spans)
    starts = [a for a, _ in spans]

    def at(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < spans[i][1]
    return at


def owner_of(r):
    """``leaf -> "gen/decode" | "gen/prefill" | None``: the region of
    the loop a leaf lies under, if either — by its name where the name
    says it (a call's children, a chunk's rows: so also in a group whose
    ``gen/prefill`` opened before the trace did), else by the time."""
    under = {name: _holder(program_spans.named(r, name))
             for name in OWNERS}

    def owner(leaf):
        for name in OWNERS:
            if leaf.name.startswith(name + "/") or under[name](leaf.start):
                return name
        return None
    return owner


def leaf_idle(r):
    """``{"gen/decode" | "gen/prefill" | None: seconds}``: device-idle
    time inside the leaves under each owner, each leaf cut to the traced
    window; None where the run has no regions or no device events."""
    spans = program_spans.spans_of(r)
    if not spans or not r.trace.devices:
        return None
    lo, hi = r.trace.extent
    busy = trace_reduce.busy_cover(r.trace)
    owner = owner_of(r)
    out = dict.fromkeys(OWNERS + (None,), 0.0)
    for s in spans:
        a, b = max(lo, s.start), min(hi, s.start + s.dur)
        if s.leaf and a < b:
            out[owner(s)] += (b - a) - busy.within(a, b)
    return out


def idle_ms_per(r, owner, per):
    """Device-idle ms inside the leaves under ``owner`` (None: under no
    owner), per region called ``per``; None where either is missing."""
    n = len(program_spans.named(r, per))
    idle = leaf_idle(r) if n else None
    return None if idle is None else 1e3 * idle[owner] / n


def _steps_with(r, key):
    return [s for s in program_spans.named(r, "gen/step") if key in s.stats]


def offcpu_ms(r):
    """Mean over the steps that carry ``cpu_us``: the step's wall time
    minus its CPU time minus the wall time of its ``/fetch`` regions, in
    ms — the time the thread was on no CPU and not waiting for its
    programs' token ids: the GIL, a lock, the scheduler, and blocking in
    the runtime's staging and dispatch too.  A lower bound: CPU time
    spent inside a fetch is subtracted as well."""
    steps = _steps_with(r, "cpu_us")
    if not steps:
        return None
    fetches = sorted((s.start, s.dur) for s in program_spans.spans_of(r)
                     if s.name.endswith("/fetch"))
    starts = [a for a, _ in fetches]
    total = 0.0
    for st in steps:
        i = bisect.bisect_left(starts, st.start)
        j = bisect.bisect_left(starts, st.start + st.dur)
        waited = sum(d for _, d in fetches[i:j])
        total += st.dur - 1e-6 * st.stats["cpu_us"] - waited
    return 1e3 * total / len(steps)


def gc_pause_ms(r):
    """Mean ``gc_us`` per step that carries it, in ms."""
    steps = _steps_with(r, "gc_us")
    if not steps:
        return None
    return 1e-3 * sum(s.stats["gc_us"] for s in steps) / len(steps)
