"""The serving programs' calls one by one, for the readers of a cell whose
device holds a share of an expert-parallel layer (``ep_*``).

A region's device events are those that start inside it (the call waits
for its outputs, so its program runs inside it and no other does).  A
call whose events the trace holds only in part — the profiler's buffer
dropped some, or the call straddles the traced stretch's edge — would
set its full counts against part of its time, so each reader keeps the
WHOLE calls alone: those of a program that ran as many device
operations as that program's calls most often did.  Every call of one
program runs the same operations, so the rest are the ones cut short.
"""
import bisect
import collections

from . import program_spans, region_ops, trace_reduce

DECODE, PREFILL = "gen/decode", "gen/prefill/call"


def _text_key(region, span):
    if region == DECODE:
        return "decode"
    if "rows" not in span.stats or "bucket" not in span.stats:
        return None
    return f"{int(span.stats['rows'])}x{int(span.stats['bucket'])}"


def _text(r, key):
    if key == "decode":
        return r.facts.get("hlo_text")
    return (r.facts.get("prefill_hlo_texts") or {}).get(key)


def whole_calls(r, region):
    """``[(span, events, busy_s, program text)]`` of the whole calls of
    ``region`` (``DECODE`` or ``PREFILL``), each with the first device's
    events ``(name, start, dur)`` that start inside it and the device's
    busy seconds within it; None where there is nothing to read."""
    memo_key = ("ep_calls.whole_calls", region)
    if memo_key in r.trace.memo:
        return r.trace.memo[memo_key]
    spans = program_spans.named(r, region)
    out = None
    if spans and r.trace.devices:
        evs = r.trace.devices[sorted(r.trace.devices)[0]]
        starts = [e[1] for e in evs]
        busy = trace_reduce.busy_cover(r.trace)
        calls = []
        for s in spans:
            key = _text_key(region, s)
            text = _text(r, key) if key else None
            if text is None:
                continue
            lo = bisect.bisect_left(starts, s.start)
            hi = bisect.bisect_left(starts, s.start + s.dur)
            calls.append((key, s, evs[lo:hi],
                          busy.within(s.start, s.start + s.dur), text))
        # the count of operations a program's calls most often ran
        modes = {}
        for key in {c[0] for c in calls}:
            counts = collections.Counter(
                len(c[2]) for c in calls if c[0] == key)
            modes[key] = max(counts, key=lambda n: (counts[n], n))
        out = [c[1:] for c in calls if c[2] and len(c[2]) == modes[c[0]]]
        out = out or None
    r.trace.memo[memo_key] = out
    return out


def scope_names(r, text, scope):
    """Names of ``text``'s instructions wholly under ``scope``."""
    key = ("ep_calls.scope_names", id(text), scope)
    if key not in r.trace.memo:
        r.trace.memo[key] = program_spans.ops_by_scope(
            region_ops._constants_unnamed(text), scope)[0]
    return r.trace.memo[key]
